"""The FLOPs that the benchmark counts for one engine step
(``chipbench.counts.step_flops``, the numerator of ``step_mfu``) against the
FLOPs of the compiled step the cells serve, counted from its HLO by
``launch/hlo_analysis``."""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import counts  # noqa: E402
from chipbench.run import repo_config  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.models import decode  # noqa: E402
from repro.models import params as MP  # noqa: E402

# both sides count the matmuls of the weights and attention's QK^T and PV;
# what they leave to differ is small (norms, RoPE, the softmax)
FLOPS_RTOL = 0.02


def test_counted_step_flops_match_the_compiled_served_step():
    sizes = json.loads((ROOT / "chipbench/configs/qwen2-0.5b.json").read_text())
    cfg = repo_config(sizes)
    slots, max_len = 4, 64
    hlo = decode.make_serve_step(cfg).lower(
        MP.param_specs(cfg), decode.cache_specs(cfg, slots, max_len),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    got = hlo_analysis.analyze(hlo).flops
    want = counts.step_flops(sizes, slots, max_len - 1)
    assert abs(want - got) <= FLOPS_RTOL * got, (want, got)
