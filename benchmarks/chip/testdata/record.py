"""Record the small profiler trace that ``tests/test_trace_reduce.py``
reads: three ``round`` spans inside a ``window`` span, each running a
jitted matmul and an elementwise pass, with 50 ms of host sleep between
them, so the device is idle between rounds.

    python benchmarks/chip/testdata/record.py    # on a machine with a chip

Writes ``small.xplane.pb`` and ``small.json`` (the ``perf_counter_ns``
reading taken as each round span opened, and the sleep) beside this file.
"""

import json
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent
SLEEP_S = 0.05


def main() -> None:
    mm = jax.jit(lambda a: a @ a)
    ew = jax.jit(lambda a: jnp.tanh(a) * 2.0)
    x = jnp.ones((2048, 2048), jnp.float32)
    jax.block_until_ready(ew(mm(x)))          # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = Path(tempfile.mkdtemp())
    starts = []
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            starts.append(time.perf_counter_ns())
            with jax.profiler.TraceAnnotation("round"):
                jax.block_until_ready(ew(mm(x)))
                time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    src = sorted(tmp.rglob("*.xplane.pb"))[-1]
    shutil.copy(src, HERE / "small.xplane.pb")
    (HERE / "small.json").write_text(json.dumps(
        {"round_starts_ns": starts, "sleep_s": SLEEP_S,
         "device_kind": jax.devices()[0].device_kind}))
    shutil.rmtree(tmp)
    print(f"wrote {HERE / 'small.xplane.pb'} "
          f"({(HERE / 'small.xplane.pb').stat().st_size} B)")


if __name__ == "__main__":
    main()
