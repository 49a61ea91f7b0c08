"""Record the small TPU trace that ``test_trace.py`` reduces.

    python bench/tests/record_trace.py <out.xplane.pb>

On one chip: a ``bench.window`` annotation around 20 calls of the served
scorer at one block shape, each preceded by 2 ms of host work under a
``bench.pack`` annotation, so the device idles between calls.  Prints the
trace's reduction as JSON.
"""

import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))


def main(out: str) -> None:
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    import trace as trace_mod
    from repro.core.vectorized import bm25_topk

    rng = np.random.default_rng(0)
    di = jnp.asarray(rng.integers(0, 16384, (16, 8, 2048), dtype=np.int32))
    im = jnp.asarray(rng.random((16, 8, 2048), dtype=np.float32))
    qm = jnp.ones((16, 8), jnp.float32)
    jax.block_until_ready(bm25_topk(di, im, qm, n_docs=16384, k=10))
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(20):
                with jax.profiler.TraceAnnotation("bench.pack"):
                    t = time.perf_counter()
                    while time.perf_counter() - t < 0.002:
                        pass
                jax.block_until_ready(bm25_topk(di, im, qm, n_docs=16384,
                                                k=10))
        jax.profiler.stop_trace()
        src = trace_mod.find_xplane(Path(tmp))
        Path(out).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(trace_mod.reduce(out)))


if __name__ == "__main__":
    main(sys.argv[1])
