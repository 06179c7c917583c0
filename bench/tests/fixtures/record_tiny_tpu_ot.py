"""Record ``tiny_tpu_ot.xplane.pb``: three ``solve(spec)`` calls on a TPU
under the profiler, each in the benchmark's ``bench.call`` and
``bench.block`` spans, with the program's own ``ot.*`` spans inside.

    python3 bench/tests/fixtures/record_tiny_tpu_ot.py record RAW   # on a TPU
    python3 bench/tests/fixtures/record_tiny_tpu_ot.py trim RAW     # anywhere

``record`` writes the profiler's file to RAW. ``trim`` writes the fixture
from it, keeping every device plane and, of the host, the calling thread's
line (the one holding ``bench.call``), which is what ``tracefile`` and
``spans`` read; it needs the XPlane protobuf module that TensorFlow ships.

n = m = 4096 points in R^8, r = 256 anchors: over the megakernel's VMEM
budget, so the loop runs the per-iteration log kernels, as the HIGGS cell
does; ``max_iter`` = 24 keeps the file small.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import tracefile  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "tiny_tpu_ot.xplane.pb")


def spec(seed: int):
    import jax
    from repro.core.geometry import GaussianPointCloud
    from repro.core.spec import SolveSpec
    kx, ky, ka = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (4096, 8)) * 0.5
    y = jax.random.normal(ky, (4096, 8)) * 0.4 + 0.1
    anchors = jax.random.normal(ka, (256, 8)) * 0.5
    geom = GaussianPointCloud.build(x, y, anchors, eps=1.0, R=4.0)
    return SolveSpec(geometry=geom, method="log_factored", tol=1e-6,
                     max_iter=24)


def record(raw: str, require_tpu: bool = True) -> int:
    import jax
    from repro.core import solve
    if require_tpu and jax.devices()[0].platform != "tpu":
        print("record_tiny_tpu_ot: no TPU", file=sys.stderr)
        return 2
    specs = [spec(i) for i in range(3)]
    jax.block_until_ready(solve(specs[0]))          # warm-up
    trace_dir = tempfile.mkdtemp(prefix="ot_fixture_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        for s in specs:
            with jax.profiler.TraceAnnotation(tracefile.CALL_SPAN):
                res = solve(s)
            with jax.profiler.TraceAnnotation(tracefile.BLOCK_SPAN):
                jax.block_until_ready(res)
        jax.profiler.stop_trace()
        shutil.copy(tracefile.find_xplane(trace_dir), raw)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"{raw}: {os.path.getsize(raw)} bytes")
    return 0


def trim(raw: str, out: str = OUT) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(raw, "rb") as fh:
        space.ParseFromString(fh.read())
    keep = []
    for plane in space.planes:
        if plane.name.startswith("/device:"):
            keep.append(plane)
        elif plane.name.startswith("/host:") and plane.lines:
            names = {k: m.name for k, m in plane.event_metadata.items()}
            lines = [line for line in plane.lines
                     if any(names.get(e.metadata_id) == tracefile.CALL_SPAN
                            for e in line.events)]
            del plane.lines[:]
            plane.lines.extend(lines)
            keep.append(plane)
    del space.planes[:]
    space.planes.extend(keep)
    with open(out, "wb") as fh:
        fh.write(space.SerializeToString())
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    mode, raw = sys.argv[1:3]
    sys.exit(record(raw) if mode == "record" else trim(raw))
