#!/usr/bin/env python3
"""Record `data/scoped.xplane.pb.gz`, the trace `test_scopes.py` reads.

    python3 bench/tests/record_scoped_trace.py <output .xplane.pb.gz>

Run it on a TPU.  Inside a `bench.window` span it makes two
`run_mc_detector` calls of 4 dies in chunks of 2 at the smoke geometry
(`yolo_irc.smoke()`), each inside a `bench.run_mc_detector` span, as the
benchmark's population runner does: a scoped chunk program (`sample`,
`stem`, `s0b0`, `s0pool`, `s1b0`, `s1pool`, `head`) between the program's
`repro.mc.*` host spans.  The trace is then cut to what the reduction
reads, so that the file stays small: the host's `bench.*`/`repro.*` spans
and its `DoEnqueueProgram`/`CompleteCallbacks` events, the chips'
`XLA Modules` and `XLA Ops` lines, and of each op's metadata only its
`tf_op`.  The cut needs `tensorflow`'s copy of the XSpace protobuf, and
runs in this process after a child process that holds the chip has
recorded the trace and exited.
"""
import gzip
import subprocess
import sys
import tempfile
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT / "src"))

HOST_EVENTS = ("DoEnqueueProgram", "CompleteCallbacks")
DEVICE_LINES = ("XLA Modules", "XLA Ops")


def record(trace_dir: Path) -> None:
    import jax
    import numpy as np

    from repro.configs import yolo_irc
    from repro.data.detection import SyntheticDetectionData
    from repro.mc import McConfig, run_mc_detector
    from repro.models import IRCDetector

    if jax.devices()[0].platform != "tpu":
        sys.exit("record the trace on a TPU")
    cfg = yolo_irc.smoke()
    det = IRCDetector(cfg)
    data = SyntheticDetectionData(cfg.img_hw, cfg.n_classes, cfg.n_anchors,
                                  cfg.strides, seed=1)
    batch = data.batch_for_step(0, 2)
    params = det.calibrate_bn(det.init(jax.random.PRNGKey(0)), batch.images)
    boxes = [np.zeros((0, 4), np.float32)] * 2
    classes = [np.zeros((0,), np.int32)] * 2
    mc = McConfig(n_chips=4, chunk_size=2)
    call = lambda i: run_mc_detector(jax.random.PRNGKey(i), det, params,
                                     batch.images, boxes, classes, mc=mc)
    call(0)                                      # compile outside the trace
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(2):
            with jax.profiler.TraceAnnotation("bench.run_mc_detector"):
                call(i)
    jax.profiler.stop_trace()


def cut(xplane: Path) -> bytes:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace.FromString(xplane.read_bytes())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        host = plane.name.startswith("/host:CPU")
        if not (host or plane.name.startswith("/device:TPU")):
            continue
        kept = out.planes.add()
        kept.CopyFrom(plane)
        kept.ClearField("lines")
        names = plane.event_metadata
        for line in plane.lines:
            if not host and line.name not in DEVICE_LINES:
                continue
            new = kept.lines.add()
            new.CopyFrom(line)
            new.ClearField("events")
            for ev in line.events:
                name = names[ev.metadata_id].name
                if not host or name.startswith(("bench.", "repro.")) \
                        or name in HOST_EVENTS:
                    new.events.add().CopyFrom(ev)
        tf_op = [i for i, m in plane.stat_metadata.items()
                 if m.name == "tf_op"]
        used = {ev.metadata_id for line in kept.lines for ev in line.events}
        kept.ClearField("event_metadata")
        for i in used:
            md = kept.event_metadata[i]
            md.CopyFrom(names[i])
            md.ClearField("stats")
            for st in names[i].stats:
                if st.metadata_id in tf_op:
                    md.stats.add().CopyFrom(st)
    return out.SerializeToString()


def main(out: str) -> None:
    with tempfile.TemporaryDirectory() as tmp:
        subprocess.run([sys.executable, __file__, "--record", tmp],
                       check=True)
        xplane = sorted(Path(tmp).rglob("*.xplane.pb"),
                        key=lambda p: p.stat().st_mtime)[-1]
        data = cut(xplane)
    with gzip.open(out, "wb") as f:
        f.write(data)
    print(f"wrote {out}: {len(data)} bytes before gzip")


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--record":
        record(Path(sys.argv[2]))
    elif len(sys.argv) == 2:
        main(sys.argv[1])
    else:
        sys.exit(__doc__.split("\n\n")[1])
