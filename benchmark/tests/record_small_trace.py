"""Records the small device trace kept beside the tests (run once on the chip):

    python benchmark/tests/record_small_trace.py chiprun_out/small_trace

Two annotated host spans around a few small jitted calls, with a sleep between
them so that the reduction has an idle gap to name.
"""

import glob
import os
import shutil
import sys
import time


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    for name in ("inside a", "inside b"):
        with jax.profiler.TraceAnnotation(name):
            for _ in range(3):
                step(x).block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(os.path.join(out_dir, "plugins"))
    data = jax.profiler.ProfileData.from_file(os.path.join(out_dir, "small.xplane.pb"))
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events),
                  [(e.name, e.start_ns, e.duration_ns) for e in events[:4]])
    print("device", jax.devices()[0].platform, jax.devices()[0].device_kind,
          "cache_env", os.environ.get("JAX_COMPILATION_CACHE_DIR"))


if __name__ == "__main__":
    main(sys.argv[1])
