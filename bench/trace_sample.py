"""Shrink a recorded profiler trace to what the benchmark's reductions
read, for a small test fixture (``bench.tune trace`` records one).

    python3 -m bench.trace_sample IN.xplane.pb OUT.xplane.pb

Kept: on the host plane ``/host:CPU`` the spans named ``bench.*`` and
``serve.*`` and the runtime's ``DoEnqueueProgram`` events; on every TPU
plane the lines ``XLA Modules`` and ``XLA Ops``.  Every other event is
dropped (its plane and line stay, empty), and so is the metadata of events
no longer there.  The ``XSpace`` message is read with the protobuf module
that the installed TensorFlow ships, loaded from its file alone.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

HOST_KEEP = ("bench.", "serve.", "DoEnqueueProgram")
DEVICE_LINES = ("XLA Modules", "XLA Ops")


def _xplane_pb2():
    tf = importlib.util.find_spec("tensorflow")
    if tf is None or not tf.submodule_search_locations:
        raise SystemExit("the XSpace protobuf module comes with TensorFlow, "
                         "which is not installed")
    path = (Path(tf.submodule_search_locations[0])
            / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _kept(plane, line, event) -> bool:
    if plane.name == "/host:CPU":
        name = plane.event_metadata[event.metadata_id].name
        return name.startswith(HOST_KEEP)
    return plane.name.startswith("/device:TPU:") and line.name in DEVICE_LINES


def shrink(data: bytes) -> bytes:
    space = _xplane_pb2().XSpace()
    space.ParseFromString(data)
    for plane in space.planes:
        used = set()
        for line in plane.lines:
            events = [e for e in line.events if _kept(plane, line, e)]
            del line.events[:]
            line.events.extend(events)
            used.update(e.metadata_id for e in events)
        for mid in [m for m in plane.event_metadata if m not in used]:
            del plane.event_metadata[mid]
    return space.SerializeToString()


def main(argv=None) -> int:
    src, dst = (argv if argv is not None else sys.argv[1:])
    Path(dst).write_bytes(shrink(Path(src).read_bytes()))
    print(f"{dst}: {Path(dst).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
