"""Record ``data/tiny_scoped.xplane.pb``: a profiler trace of four engine
steps of the small qwen2 (two layers of width 64, ``chipbench_small``), with
the served step's named scopes and the engine's ``serve.*`` spans.

    python3 tests/chipbench/record_scoped_fixture.py <out.xplane.pb>

on a machine with a TPU, from the root of a checkout.  The trace opens
after step 3 of one wave and closes after step 7, inside a
``traced_window`` span, as ``chipbench/run.py`` traces a cell.  The
committed file then had the absolute source paths it carries made
relative to the checkout and its metadata plane's embedded HLO protos
dropped; ``chipbench/scopes.py`` reads neither.
"""
import pathlib
import shutil
import sys
import tempfile

import numpy as np

import chipbench_small as S


def main(out):
    import jax
    from chipbench import driver, gen, run, weights, xtrace
    from repro.launch import serve
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_fixture: no TPU", file=sys.stderr)
        return 2
    sizes = S.sizes("qwen2-0.5b")
    cfg = run.repo_config(sizes)
    params = weights.make(cfg, 0)
    reqs = gen.wave(S.TRAFFIC, np.random.default_rng(0), sizes["vocab_size"])
    driver.run(serve, cfg, params, reqs, S.TRAFFIC["max_len"], max_steps=2)
    tmp = tempfile.mkdtemp()
    span = []

    def on_step(n):
        if n == 3:
            jax.profiler.start_trace(tmp)
            span.append(jax.profiler.TraceAnnotation(xtrace.WINDOW))
            span[0].__enter__()
        elif n == 7:
            span[0].__exit__(None, None, None)
            jax.profiler.stop_trace()

    wave = driver.run(serve, cfg, params, reqs, S.TRAFFIC["max_len"], on_step=on_step)
    assert len(wave.t_end) > 7 and wave.failed == 0
    shutil.copyfile(xtrace.find_xplane(tmp), out)
    print(f"{out}: {pathlib.Path(out).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
