"""Cut a recorded `.xplane.pb` down to a test fixture: the device's "XLA
Modules" and "XLA Ops" lines and the host's `bench:*` spans, for the first
`seconds` of the span `bench:window`.

    python3 benchmarks/tests/trim_trace.py <in.xplane.pb> <out.xplane.pb> [seconds]
"""

from __future__ import annotations

import json
import sys

from jax.profiler import ProfileData


def trim(src: str, dst: str, seconds: float = 0.7) -> int:
    data = ProfileData.from_file(src)
    planes = list(data.planes)
    t0 = min(ev.start_ns for p in planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events
             if ev.name == "bench:window")
    t1 = t0 + seconds * 1e9
    out, kept = [], 0
    for pid, plane in enumerate(planes):
        device = plane.name.startswith("/device:TPU:")
        if not device and not plane.name.startswith("/host:"):
            continue
        names: dict[str, int] = {}
        lines = []
        for lid, line in enumerate(plane.lines):
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            events = []
            for ev in line.events:
                if not device and not ev.name.startswith("bench:"):
                    continue
                start, end = max(ev.start_ns, t0), min(ev.start_ns + ev.duration_ns, t1)
                if end <= start:
                    continue
                mid = names.setdefault(ev.name, len(names) + 1)
                events.append(f"events {{ metadata_id: {mid} offset_ps: "
                              f"{int((start - t0) * 1000)} duration_ps: "
                              f"{int((end - start) * 1000)} }}")
            if events:
                kept += len(events)
                lines.append(f'lines {{ id: {lid + 1} name: "{line.name}" '
                             f"timestamp_ns: 0 {' '.join(events)} }}")
        if lines:
            meta = " ".join(
                f"event_metadata {{ key: {i} value {{ id: {i} name: {json.dumps(n)} }} }}"
                for n, i in names.items())
            out.append(f'planes {{ id: {pid + 1} name: "{plane.name}" '
                       f"{' '.join(lines)} {meta} }}")
    with open(dst, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(" ".join(out)))
    return kept


if __name__ == "__main__":
    print(trim(sys.argv[1], sys.argv[2], *map(float, sys.argv[3:4])), "events kept")
