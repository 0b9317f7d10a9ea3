"""What one engine step needs, counted from shapes: FLOPs and HBM bytes.

These count the algorithm, not what the program does today: attention over
the live positions of occupied slots only, every weight read once, and each
expert read only if some token is routed to it.  ``sizes`` is a
configuration file's dict (published key names).

An architecture's reference module (``chipbench/reference/``) may count its
own step: ``step_flops`` and ``step_bytes`` here then return what its
functions of the same names give.  Otherwise the step is counted as a
decoder of attention with a K/V cache and a SiLU-gated MLP, or routed
experts where the file has ``num_experts`` (``decoder_step_flops``,
``decoder_step_bytes``, which such a module may call for the parts it
shares).
"""
import json
import pathlib

from chipbench import reference

BF16 = 2


def peaks(device_kind):
    """Per-chip peaks for ``device_kind``; an unknown device is an error."""
    table = json.loads((pathlib.Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table["devices"][device_kind]


def _dims(s):
    return (s["num_hidden_layers"], s["hidden_size"], s["num_attention_heads"],
            s["num_key_value_heads"], s["head_dim"], s["intermediate_size"],
            s["vocab_size"])


def attn_params(s):
    _, d, h, hkv, dh, _, _ = _dims(s)
    return d * h * dh + 2 * d * hkv * dh + h * dh * d


def expert_params(s):
    """One SiLU-gated FFN (dense MLP or one expert): w_gate, w_up, w_down."""
    return 3 * s["hidden_size"] * s["intermediate_size"]


def matmul_params_per_token(s):
    """Weights a token multiplies: per layer attention + MLP (or router and
    its top-k experts), and the output head once."""
    n_layers, d, _, _, _, _, v = _dims(s)
    if s.get("num_experts"):
        ffn = d * s["num_experts"] + s["num_experts_per_tok"] * expert_params(s)
    else:
        ffn = expert_params(s)
    return n_layers * (attn_params(s) + ffn) + d * v


def step_flops(s, n_occ, pos):
    """FLOPs of one step with ``n_occ`` occupied slots at position ``pos``."""
    own = getattr(reference.for_config(s), "step_flops", None)
    return own(s, n_occ, pos) if own else decoder_step_flops(s, n_occ, pos)


def step_bytes(s, n_occ, pos):
    """HBM bytes one step with ``n_occ`` occupied slots at position ``pos``
    needs."""
    own = getattr(reference.for_config(s), "step_bytes", None)
    return own(s, n_occ, pos) if own else decoder_step_bytes(s, n_occ, pos)


def decoder_step_flops(s, n_occ, pos):
    """FLOPs of one step with ``n_occ`` occupied slots at position ``pos``:
    2 per multiply-add of the weights, and QK^T plus PV over pos + 1 keys."""
    n_layers, _, h, _, dh, _, _ = _dims(s)
    attn = n_layers * 4 * h * dh * (pos + 1)
    return n_occ * (2 * matmul_params_per_token(s) + attn)


def experts_touched(s, n_occ):
    """Expected number of distinct experts of one layer that ``n_occ`` tokens
    route to, each picking k of E uniformly."""
    e, k = s["num_experts"], s["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** n_occ)


def decoder_step_bytes(s, n_occ, pos):
    """HBM bytes one step needs: every weight once (the experts touched, the
    output head, the embedding rows looked up when the head is untied), the
    K/V of positions <= pos read and one K/V position written per occupied
    slot."""
    n_layers, d, _, hkv, dh, _, v = _dims(s)
    layer = attn_params(s) + 2 * d                      # + two norm gains
    if s["qkv_bias"]:
        layer += (s["num_attention_heads"] + 2 * hkv) * dh
    if s.get("num_experts"):
        layer += d * s["num_experts"] + experts_touched(s, n_occ) * expert_params(s)
    else:
        layer += expert_params(s)
    weights = n_layers * layer + d * v + d               # head + final norm
    if not s["tie_word_embeddings"]:
        weights += n_occ * d                            # embedding rows
    kv = n_layers * n_occ * 2 * hkv * dh * (pos + 2)    # pos + 1 read, 1 write
    return BF16 * (weights + kv)
