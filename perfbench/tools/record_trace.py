"""Record the small device trace that tests/test_trace_reduce.py checks the
reduction on. Run once on the chip (it needs one); the output is committed.

    python3 perfbench/tools/record_trace.py chiprun_out/small.xplane.pb
"""
import glob
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    def body(x, _):
        return jnp.tanh(x @ x) * 0.5, None

    @jax.jit
    def scan_step(x):
        return jax.lax.scan(body, x, None, length=8)[0]

    @jax.jit
    def flat_step(x):
        return (x @ x).sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    scan_step(x).block_until_ready()
    flat_step(x).block_until_ready()
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                scan_step(x).block_until_ready()
                flat_step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_wait"):
                time.sleep(0.02)
        jax.profiler.stop_trace()
        found = glob.glob(tmp + "/plugins/profile/*/*.xplane.pb")
        shutil.copy(found[0], out)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(out).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events),
                  [(e.name, int(e.start_ns), int(e.duration_ns)) for e in events[:4]])
            if events and plane.name.startswith("/device") and line.name in ("XLA Ops",):
                print("    STATS", [(k, v) for k, v in list(events[0].stats)[:12]])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
