"""Profiler-trace summarization — reads ``jax.profiler`` xplane dumps.

The reference's observability is TensorBoard scalars plus ad-hoc timing
logs (SURVEY.md §5); the TPU-native story is `Estimator.set_profile`
writing real `jax.profiler` traces. Those traces are XSpace protobufs
that normally need the TensorBoard profile plugin to open; this module
gives a dependency-free summary path: the shared wire codec
(common/wire.py, also under onnx/proto.py) walks the XSpace schema and
aggregates per-device op time by category, so "where did the step
go" is one function call instead of a TensorBoard deployment.

Both public views — :func:`summarize_trace` (per-line category rollup)
and :func:`top_ops` (per-op totals) — walk the schema through ONE parser
(:func:`_iter_planes` / :func:`_line_events`), so they cannot disagree
about what an event's name or duration is (their agreement on the same
trace is pinned in tests/test_trace_tools.py).

Caveat: events on the copy/async lines are *overlapping async spans*, not
exclusive busy time — compare categories within a line, don't sum lines
into wall time.
"""

from __future__ import annotations

import glob
import os
from collections import Counter
from typing import Dict, Iterator, List, Tuple

from analytics_zoo_tpu.common.wire import iter_fields as _fields


def _categorize(name: str) -> str:
    for key in ("convolution", "fusion", "copy", "all-reduce", "all-gather",
                "reduce-scatter", "all-to-all", "collective-permute", "slice",
                "dot", "custom-call", "infeed", "outfeed"):
        if key in name:
            return key
    return "other"


# ---------------------------------------------------------------------------
# The one xplane walk (XSpace -> planes -> lines -> events) both public
# views are built on.
# ---------------------------------------------------------------------------


def _newest_dump(log_dir: str) -> bytes:
    pbs = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)
    if not pbs:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return open(pbs[-1], "rb").read()


def _iter_planes(data: bytes) -> Iterator[Tuple[str, List[bytes],
                                                Dict[int, str]]]:
    """Yield ``(plane_name, line_buffers, event_names)`` per XPlane:
    the plane's name, its raw XLine submessages, and the
    metadata-id -> event-name map the lines' events reference."""
    for fn, wt, plane in _fields(data):
        if fn != 1 or wt != 2:
            continue
        pname, lines, ev_names = "", [], {}
        for f2, w2, v2 in _fields(plane):
            if f2 == 2 and w2 == 2:
                pname = v2.decode(errors="replace")
            elif f2 == 3 and w2 == 2:
                lines.append(v2)
            elif f2 == 4 and w2 == 2:  # map<int64, XEventMetadata>
                mid, meta = None, None
                for f3, _w3, v3 in _fields(v2):
                    if f3 == 1:
                        mid = v3
                    elif f3 == 2:
                        meta = v3
                if meta is not None:
                    nid, nname = mid, ""
                    for f4, w4, v4 in _fields(meta):
                        if f4 == 1 and w4 == 0:
                            nid = v4
                        elif f4 == 2 and w4 == 2:
                            nname = v4.decode(errors="replace")
                    ev_names[nid] = nname
        yield pname, lines, ev_names


def _line_events(line_buf: bytes) -> Tuple[str, List[Tuple[int, int]]]:
    """Parse one XLine buffer into ``(line_name, [(metadata_id,
    duration_ps), ...])``."""
    lname, events = "", []
    for f2, w2, v2 in _fields(line_buf):
        if f2 == 2 and w2 == 2:
            lname = v2.decode(errors="replace")
        elif f2 == 4 and w2 == 2:
            mid = dur = 0
            for f3, w3, v3 in _fields(v2):
                if f3 == 1 and w3 == 0:
                    mid = v3
                elif f3 == 3 and w3 == 0:
                    dur = v3
            events.append((mid, dur))
    return lname, events


# ---------------------------------------------------------------------------
# Public views
# ---------------------------------------------------------------------------


def summarize_trace(log_dir: str) -> Dict[str, Dict]:
    """Aggregate the newest trace under ``log_dir``.

    Returns ``{plane_name: {"lines": {line_name: {"events": n,
    "total_ms": t, "by_category": {cat: ms}}}}}`` for device planes.
    """
    out: Dict[str, Dict] = {}
    for pname, lines, ev_names in _iter_planes(_newest_dump(log_dir)):
        plane_out: Dict[str, Dict] = {}
        for lb in lines:
            lname, events = _line_events(lb)
            if not events:
                continue
            cats: Counter = Counter()
            for mid, dur in events:
                cats[_categorize(ev_names.get(mid, ""))] += dur
            # thread-pool lines (and planes below) often share a name —
            # aggregate rather than overwrite, or data silently drops
            slot = plane_out.setdefault(
                lname, {"events": 0, "total_ms": 0.0, "by_category": Counter()})
            slot["events"] += len(events)
            slot["total_ms"] += sum(d for _, d in events) / 1e9
            slot["by_category"].update(
                {k: v / 1e9 for k, v in cats.items()})
        if plane_out:
            for slot in plane_out.values():
                slot["by_category"] = dict(slot["by_category"].most_common())
            agg = out.setdefault(pname, {"lines": {}})
            for lname, slot in plane_out.items():
                prev = agg["lines"].get(lname)
                if prev is None:
                    agg["lines"][lname] = slot
                else:
                    prev["events"] += slot["events"]
                    prev["total_ms"] += slot["total_ms"]
                    merged = Counter(prev["by_category"])
                    merged.update(slot["by_category"])
                    prev["by_category"] = dict(merged.most_common())
    return out


def print_trace_summary(log_dir: str) -> None:
    """Human-readable dump of :func:`summarize_trace`."""
    for pname, plane in summarize_trace(log_dir).items():
        print(f"plane {pname}")
        for lname, line in plane["lines"].items():
            print(f"  line '{lname}': {line['events']} events, "
                  f"{line['total_ms']:.2f} ms")
            for cat, ms in line["by_category"].items():
                print(f"      {ms:9.3f} ms  {cat}")


def top_ops(log_dir: str, line: str = "XLA Ops", n: int = 25,
            plane_substr: str = "TPU"):
    """The top-``n`` individual ops by total device time in the newest
    trace under ``log_dir`` — one level finer than
    :func:`summarize_trace`'s categories.

    This is the op-level diff view: capture two traces, ``top_ops`` both,
    and compare per-op totals. Returns ``[(name, total_ms, count), ...]``
    sorted by time. ``line`` picks the trace line ("XLA Ops" =
    exclusive device busy time; "Async XLA Ops" = overlapping async
    spans — compare within a line, never sum lines). ``plane_substr``
    filters device planes ("TPU", or "CPU" for interpret runs)."""
    totals: Counter = Counter()
    counts: Counter = Counter()
    for pname, lines, ev_names in _iter_planes(_newest_dump(log_dir)):
        if plane_substr not in pname:
            continue
        for lb in lines:
            lname, events = _line_events(lb)
            if lname != line:
                continue
            for mid, dur in events:
                name = ev_names.get(mid, "?")
                totals[name] += dur
                counts[name] += 1
    return [(name, ps / 1e9, counts[name])
            for name, ps in totals.most_common(n)]
