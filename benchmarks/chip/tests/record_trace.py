"""Record the small device trace that ``test_trace_reduce.py`` reads.

    python3 benchmarks/chip/tests/record_trace.py <out.xplane.pb>

Run on a TPU machine from the checkout root. Four iterations of a jitted
``step`` that calls the program's window-pair kernel, each inside a
``bench.retrieve_batch`` annotation with a host sleep between them, all in
the ``bench.window`` annotation after a ``bench.anchor`` mark, as the
harness records its windows. Prints the anchor's ``perf_counter_ns``
reading and the number of iterations.
"""
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ops  # noqa: E402
from repro.sampling.pairs import window_positions  # noqa: E402

STEPS = 4


def main(out: str) -> None:
    pos = window_positions(6, 2)

    @jax.jit
    def step(paths, x):
        src, dst = ops.window_pair_ids(paths, pos)
        return src.sum() + dst.sum() + jnp.tanh(x @ x).sum()

    paths = jnp.arange(29 * 6, dtype=jnp.int32).reshape(29, 6)
    x = jnp.ones((512, 512), jnp.float32)
    step(paths, x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    anchor = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation("bench.anchor"):
        pass
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("bench.retrieve_batch"):
                step(paths, x).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(d)
    print(f"anchor_ns {anchor} steps {STEPS}")


if __name__ == "__main__":
    main(sys.argv[1])
