"""Record the small profiler trace that ``test_trace.py`` reduces.

    python bench/tests/record_trace.py OUT_DIR

Runs on every local TPU chip: a data-parallel least-squares step whose
gradient XLA all-reduces over a ``("data",)`` mesh, three times, each
inside the harness's host annotations (``bench.fetch`` sleeps 5 ms so the
device idles, ``bench.step`` runs the step).  It writes the trace under
OUT_DIR and prints a summary of its planes and lines.
"""
from __future__ import annotations

import collections
import glob
import json
import os
import sys
import time


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"record_trace: needs a TPU, JAX found {devs[0].platform}")
    mesh = Mesh(np.array(devs), ("data",))
    xs = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())

    @jax.jit
    def step(w, x):
        g = jax.grad(lambda w: jnp.mean(jnp.square(x @ w)))(w)
        return w - 1e-3 * g

    step = jax.jit(step, in_shardings=(rep, xs), out_shardings=rep)
    w = jax.device_put(jnp.ones((1024, 1024), jnp.float32) * 1e-3, rep)
    x = jax.device_put(jnp.ones((1024 * len(devs), 1024), jnp.float32), xs)
    w = step(w, x).block_until_ready()
    jax.profiler.start_trace(out)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.fetch", step=i):
            time.sleep(0.005)
        with jax.profiler.TraceAnnotation("bench.step", step=i):
            w = step(w, x)
            w.block_until_ready()
    jax.profiler.stop_trace()

    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                     recursive=True)[0]
    summary = {"path": path, "bytes": os.path.getsize(path), "planes": []}
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            first = evs[0] if evs else None
            lines.append({
                "line": line.name, "events": len(evs),
                "names": names.most_common(8),
                "first": None if first is None else {
                    "name": first.name, "start_ns": first.start_ns,
                    "dur_ns": first.duration_ns,
                    "stats": [(k, str(v)) for k, v in first.stats][:12]}})
        summary["planes"].append({"plane": plane.name, "lines": lines})
    print(json.dumps(summary, indent=1))
    print(json.dumps({"kind": devs[0].device_kind, "count": len(devs),
                      "memory_stats": devs[0].memory_stats(),
                      "env": {k: os.environ.get(k) for k in (
                          "JAX_COMPILATION_CACHE_DIR", "LIBTPU_INIT_ARGS",
                          "TMPDIR", "HOME", "XDG_CACHE_HOME")}}))


if __name__ == "__main__":
    main(sys.argv[1])
