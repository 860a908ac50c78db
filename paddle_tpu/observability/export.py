"""Exporters: chrome-trace JSON (Perfetto), append-only JSONL, summaries.

Chrome-trace layout: pid = rank, tid = stream lane by category (compile /
dispatch / collective / memory / fault / ...), ``X`` complete events in
microseconds, ``M`` metadata naming processes and lanes, and ``s``/``f``
flow events drawing the compile→dispatch arrow for every executable
(the compile span carries ``flow_out``, its dispatches ``flow_in``).

The JSONL sink is one ``Event.to_dict()`` JSON object per line,
append-only, for machine consumption (fleet aggregation, test replay —
``load_jsonl`` round-trips it).

``summary(view=...)`` renders the text table (op view: per-name totals;
step view: per-step per-category totals); ``phase_breakdown()`` is the
compact dict the smoke scripts (``scripts/obs_smoke.py``,
``serving_smoke.py``, ``lazy_smoke.py``, ...) and the tests read.
"""
from __future__ import annotations

import json
import os
import time

from .timeline import get_timeline, obs_dir

__all__ = ["CATEGORY_LANES", "chrome_trace", "collective_overlap_stats",
           "export_chrome_trace", "export_jsonl", "load_jsonl", "summary",
           "phase_breakdown", "pipeline_stats", "lint_summary_table"]

# tid lanes, one per category, so each stream renders as its own track
CATEGORY_LANES = {"host": 0, "compile": 1, "dispatch": 2, "collective": 3,
                  "memory": 4, "fault": 5, "amp": 6, "h2d": 7, "d2h": 8,
                  "pipeline": 9, "prefill": 10, "decode": 11,
                  "analysis": 12, "dma": 14,
                  "recovery": 15, "ckpt": 16, "fabric": 17}
_EXTRA_LANE_BASE = 18


def _lane(cat, extra):
    lane = CATEGORY_LANES.get(cat)
    if lane is None:
        lane = extra.setdefault(cat, _EXTRA_LANE_BASE + len(extra))
    return lane


def chrome_trace(events=None, process_name="paddle_tpu"):
    """Build the chrome-trace dict (``{"traceEvents": [...]}``)."""
    if events is None:
        events = get_timeline().events()
    extra_lanes = {}
    trace = []
    pids = set()
    lanes_used = {}
    for e in events:
        tid = _lane(e.cat, extra_lanes)
        pids.add(e.rank)
        lanes_used.setdefault((e.rank, tid), e.cat)
        args = dict(e.attrs or {})
        if e.step is not None:
            args["step"] = e.step
        ts_us = e.ts * 1e6
        if e.dur is not None:
            trace.append({"ph": "X", "name": e.name, "cat": e.cat,
                          "pid": e.rank, "tid": tid,
                          "ts": round(ts_us, 3),
                          "dur": round(e.dur * 1e6, 3), "args": args})
        else:
            trace.append({"ph": "i", "name": e.name, "cat": e.cat,
                          "pid": e.rank, "tid": tid,
                          "ts": round(ts_us, 3), "s": "t", "args": args})
        # flow arrows: start at the producing span's end, finish (bp=e:
        # bind to the enclosing slice) at each consumer span's start
        if e.flow_out is not None and e.dur is not None:
            trace.append({"ph": "s", "id": e.flow_out, "pid": e.rank,
                          "tid": tid, "ts": round((e.ts + e.dur) * 1e6, 3),
                          "name": "compile→dispatch", "cat": "flow"})
        if e.flow_in is not None:
            trace.append({"ph": "f", "bp": "e", "id": e.flow_in,
                          "pid": e.rank, "tid": tid,
                          "ts": round(ts_us, 3),
                          "name": "compile→dispatch", "cat": "flow"})
    meta = []
    for pid in sorted(pids):
        meta.append({"ph": "M", "name": "process_name", "pid": pid,
                     "tid": 0, "args": {"name": f"{process_name} "
                                                f"rank {pid}"}})
    for (pid, tid), cat in sorted(lanes_used.items()):
        meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                     "tid": tid, "args": {"name": cat}})
    return {"traceEvents": meta + trace, "displayTimeUnit": "ms"}


def export_chrome_trace(path=None, events=None, process_name="paddle_tpu"):
    """Serialize the timeline as chrome-trace JSON; returns the path."""
    if path is None:
        path = os.path.join(
            obs_dir(), f"trace_{os.getpid()}_{int(time.time())}.json")
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(chrome_trace(events, process_name=process_name), f)
    return path


def export_jsonl(path=None, events=None, append=True):
    """Append the timeline to a JSONL sink; returns the path."""
    if events is None:
        events = get_timeline().events()
    if path is None:
        path = os.path.join(obs_dir(), f"events_{os.getpid()}.jsonl")
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "a" if append else "w") as f:
        for e in events:
            f.write(json.dumps(e.to_dict()) + "\n")
    return path


def load_jsonl(path):
    """Read a JSONL sink back as a list of event dicts."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def summary(view="op", events=None, limit=30):
    """Text summary table.

    ``view="op"``: per-name call count / total / avg / max ms, largest
    total first.  ``view="step"``: per-step totals split by category.
    """
    if events is None:
        events = get_timeline().events()
    spans = [e for e in events if e.dur is not None]
    lines = []
    if view == "step":
        steps = {}
        cats = set()
        for e in spans:
            row = steps.setdefault(e.step, {})
            row[e.cat] = row.get(e.cat, 0.0) + e.dur * 1e3
            cats.add(e.cat)
        cats = sorted(cats)
        lines.append(f"{'Step':<8}" + "".join(f"{c + '(ms)':<16}"
                                              for c in cats))
        for step in sorted(steps, key=lambda s: (s is None, s)):
            row = steps[step]
            label = "-" if step is None else str(step)
            lines.append(f"{label:<8}" + "".join(
                f"{row.get(c, 0.0):<16.3f}" for c in cats))
    else:
        agg = {}
        for e in spans:
            tot, n, mx = agg.get(e.name, (0.0, 0, 0.0))
            d = e.dur * 1e3
            agg[e.name] = (tot + d, n + 1, max(mx, d))
        lines.append(f"{'Name':<44}{'Calls':<8}{'Total(ms)':<12}"
                     f"{'Avg(ms)':<12}{'Max(ms)':<12}")
        for name, (tot, n, mx) in sorted(agg.items(),
                                         key=lambda kv: -kv[1][0])[:limit]:
            lines.append(f"{name:<44}{n:<8}{tot:<12.3f}"
                         f"{tot / n:<12.3f}{mx:<12.3f}")
    n_instant = len(events) - len(spans)
    if n_instant:
        lines.append(f"[{n_instant} instant events: "
                     + ", ".join(sorted({e.cat for e in events
                                         if e.dur is None})) + "]")
    dropped = get_timeline().dropped if events is None else 0
    if dropped:
        lines.append(f"[{dropped} events dropped at capacity]")
    return "\n".join(lines)


def phase_breakdown(events=None):
    """Compact per-phase totals for the BENCH json: compile / dispatch /
    collective milliseconds, collective payload bytes, and the
    host↔device transfer bytes the dispatch spans recorded.

    Kernel time is not here: a Pallas kernel inside a compiled step
    runs on the device, where no host span can time it.  Kernels carry
    their names into the device trace (``pallas_tiles._kernel_span``)
    and ``benchmarks/span_reduce.py`` sums their device time by name.

    SPMD attribution: dispatch spans emitted under an active
    :class:`~..distributed.auto_parallel.sharding.MeshPlan` carry a
    ``mesh`` attr (surfaced as ``mesh``), collective spans carry the
    mesh ``axis`` they ran on (aggregated as
    ``collective_axis_<axis>_ms``/``_count``/``_bytes``), and serving
    DP engines stamp ``shard="dp<i>"`` — those lanes aggregate under
    ``shards[<shard>]`` so per-replica skew is visible in the bench.

    Multi-tenant serving attribution: prefill spans carry the owning
    request's ``tenant`` attr and the engine emits one
    ``serving.tenant.tokens`` instant per step and tenant, so
    ``tenants[<name>]`` breaks prefill time, committed tokens, and SLO
    violations down per tenant.

    Serving-fault attribution: when any ``serving.failover`` /
    ``serving.step_timeout`` / ``serving.shed`` instant fired, the
    breakdown gains ``failover_count`` / ``failover_recovery_ms`` /
    ``replays`` / ``step_timeout_count`` / ``shed_count``.

    Elastic-training attribution: ``recovery``-lane spans (mesh shrink,
    checkpoint restore) and ``ckpt``-lane spans (async snapshot capture
    + background write) aggregate into ``recovery_ms``/``recovery_count``
    and ``ckpt_ms``/``ckpt_count``, with ``device_lost_count`` counting
    ``elastic.device_lost`` instants — included only when they fired.

    Fabric attribution: ``fabric``-lane transfer spans (cross-host KV
    handoffs, serving/transport.py) aggregate into ``fabric_ms`` /
    ``fabric_count`` / ``fabric_bytes`` plus ``fabric_hidden_ratio``
    — the fraction of transfer time covered by compute spans, i.e.
    how well the fabric hid behind decode — with
    ``scale_events`` / ``cluster_failover_count`` /
    ``cluster_failover_ms`` counting the autoscaler's moves; included
    only when transfers actually ran.

    Degraded-mode attribution: ``degraded``-lane spans (the cluster
    router routing on snapshots while the coordination store is
    unreachable, serving/cluster.py) aggregate into ``degraded_ms`` /
    ``degraded_count``, with ``store_promotions`` counting
    ``store.promoted`` instants (standby store masters taking over) —
    included only when an outage actually happened."""
    if events is None:
        events = get_timeline().events()
    out = {"compile_ms": 0.0, "dispatch_ms": 0.0, "collective_ms": 0.0,
           "h2d_ms": 0.0, "d2h_ms": 0.0, "pipeline_wait_ms": 0.0,
           "prefill_ms": 0.0, "decode_ms": 0.0, "dma_ms": 0.0,
           "collective_bytes": 0, "h2d_bytes": 0, "d2h_bytes": 0,
           "dma_bytes": 0,
           "compile_count": 0, "dispatch_count": 0, "collective_count": 0,
           "h2d_count": 0, "d2h_count": 0, "pipeline_wait_count": 0,
           "prefill_count": 0, "decode_count": 0, "dma_count": 0}
    axis_keys = []
    shards = {}
    tenants = {}
    faults = {"failover_count": 0, "failover_recovery_ms": 0.0,
              "replays": 0, "step_timeout_count": 0, "shed_count": 0}
    hostkv = {"host_spill_count": 0, "host_promote_count": 0}
    elastic = {"recovery_ms": 0.0, "recovery_count": 0,
               "ckpt_ms": 0.0, "ckpt_count": 0, "device_lost_count": 0}
    fabric = {"fabric_ms": 0.0, "fabric_count": 0, "fabric_bytes": 0,
              "fabric_hidden_ratio": 0.0, "scale_events": 0,
              "cluster_failover_count": 0, "cluster_failover_ms": 0.0}
    fabric_spans = []
    degraded = {"degraded_ms": 0.0, "degraded_count": 0,
                "store_promotions": 0}
    lazy_lane = {"lazy_ms": 0.0, "lazy_flush_count": 0,
                 "lazy_nodes": 0, "lazy_cache_hits": 0}

    def _shard_row(label):
        return shards.setdefault(label, {
            "dispatch_ms": 0.0, "dispatch_count": 0,
            "prefill_ms": 0.0, "prefill_count": 0,
            "decode_ms": 0.0, "decode_count": 0,
            "collective_ms": 0.0, "collective_count": 0})

    def _tenant_row(label):
        return tenants.setdefault(label, {
            "prefill_ms": 0.0, "prefill_count": 0,
            "tokens": 0, "violations": 0})

    for e in events:
        attrs = e.attrs or {}
        if e.dur is None:
            tenant = attrs.get("tenant")
            if tenant and e.name == "serving.tenant.tokens":
                _tenant_row(str(tenant))["tokens"] += \
                    int(attrs.get("n", 0) or 0)
            elif tenant and e.name == "serving.slo_violation":
                _tenant_row(str(tenant))["violations"] += 1
            elif e.name == "serving.failover":
                faults["failover_count"] += 1
                faults["replays"] += int(attrs.get("replayed", 0) or 0)
                faults["failover_recovery_ms"] += \
                    float(attrs.get("recovery_ms", 0) or 0)
            elif e.name == "serving.step_timeout":
                faults["step_timeout_count"] += 1
            elif e.name == "serving.shed":
                faults["shed_count"] += 1
            elif e.name == "elastic.device_lost":
                elastic["device_lost_count"] += 1
            elif e.name == "fabric.scale_event":
                fabric["scale_events"] += 1
            elif e.name == "serving.cluster_failover":
                fabric["cluster_failover_count"] += 1
                fabric["cluster_failover_ms"] += \
                    float(attrs.get("recovery_ms", 0) or 0)
            elif e.name == "store.promoted":
                degraded["store_promotions"] += 1
            continue
        ms = e.dur * 1e3
        shard = attrs.get("shard")
        if shard and e.cat in ("dispatch", "prefill", "decode",
                               "collective"):
            row = _shard_row(str(shard))
            row[f"{e.cat}_ms"] += ms
            row[f"{e.cat}_count"] += 1
        tenant = attrs.get("tenant")
        if tenant and e.cat == "prefill":
            row = _tenant_row(str(tenant))
            row["prefill_ms"] += ms
            row["prefill_count"] += 1
        if e.cat == "compile":
            out["compile_ms"] += ms
            out["compile_count"] += 1
        elif e.cat == "dispatch":
            out["dispatch_ms"] += ms
            out["dispatch_count"] += 1
            out["h2d_bytes"] += int(attrs.get("h2d_bytes", 0) or 0)
            out["d2h_bytes"] += int(attrs.get("d2h_bytes", 0) or 0)
            if attrs.get("mesh"):
                out["mesh"] = str(attrs["mesh"])
            if e.name == "lazy:flush":
                # eager auto-trace lane: segment replays (core/lazy.py)
                lazy_lane["lazy_ms"] += ms
                lazy_lane["lazy_flush_count"] += 1
                lazy_lane["lazy_nodes"] += int(attrs.get("nodes", 0)
                                               or 0)
                if attrs.get("cache_hit"):
                    lazy_lane["lazy_cache_hits"] += 1
        elif e.cat == "collective":
            out["collective_ms"] += ms
            out["collective_count"] += 1
            nbytes = int(attrs.get("bytes", 0) or 0)
            out["collective_bytes"] += nbytes
            axis = attrs.get("axis")
            if axis:
                key = f"collective_axis_{axis}"
                if key + "_ms" not in out:
                    out[key + "_ms"] = 0.0
                    out[key + "_count"] = 0
                    out[key + "_bytes"] = 0
                    axis_keys.append(key + "_ms")
                out[key + "_ms"] += ms
                out[key + "_count"] += 1
                out[key + "_bytes"] += nbytes
        elif e.cat == "h2d":
            out["h2d_ms"] += ms
            out["h2d_count"] += 1
            out["h2d_bytes"] += int(attrs.get("h2d_bytes", 0) or 0)
        elif e.cat == "d2h":
            out["d2h_ms"] += ms
            out["d2h_count"] += 1
            out["d2h_bytes"] += int(attrs.get("d2h_bytes", 0) or 0)
        elif e.cat == "pipeline":
            out["pipeline_wait_ms"] += ms
            out["pipeline_wait_count"] += 1
        elif e.cat == "dma":
            # the kv:dma lane: KV-tier spills/promotes and the
            # disaggregated prefill->decode block transfers
            out["dma_ms"] += ms
            out["dma_count"] += 1
            out["dma_bytes"] += int(attrs.get("bytes", 0) or 0)
            direction = attrs.get("dir")
            if direction == "spill":
                hostkv["host_spill_count"] += 1
            elif direction == "promote":
                hostkv["host_promote_count"] += 1
        elif e.cat == "fabric":
            # cross-host KV handoff transfers (serving/transport.py):
            # spans run send -> seat, so the hidden ratio below can
            # measure how much of the wire time ran under decode
            fabric["fabric_ms"] += ms
            fabric["fabric_count"] += 1
            fabric["fabric_bytes"] += int(attrs.get("bytes", 0) or 0)
            fabric_spans.append((e.ts, e.ts + e.dur))
        elif e.cat == "degraded":
            # store-outage lane: windows the cluster router spent
            # routing on its last gossip snapshot (serving/cluster.py)
            degraded["degraded_ms"] += ms
            degraded["degraded_count"] += 1
        elif e.cat == "recovery":
            # elastic-training lane: shrink + restore spans
            elastic["recovery_ms"] += ms
            elastic["recovery_count"] += 1
        elif e.cat == "ckpt":
            # async snapshot lane: capture + background write spans
            elastic["ckpt_ms"] += ms
            elastic["ckpt_count"] += 1
        elif e.cat in ("prefill", "decode"):
            out[f"{e.cat}_ms"] += ms
            out[f"{e.cat}_count"] += 1
    for k in ("compile_ms", "dispatch_ms", "collective_ms", "h2d_ms",
              "d2h_ms", "pipeline_wait_ms", "prefill_ms", "decode_ms",
              "dma_ms", *axis_keys):
        out[k] = round(out[k], 3)
    # per-axis compute/communication overlap (tile-level overlap win):
    # overlap_ratio_<axis> = fraction of that axis's collective-span
    # time covered by compute spans, from the same event stream
    for axis, row in collective_overlap_stats(events).items():
        out[f"overlap_ratio_{axis}"] = row["overlap_ratio"]
        out[f"overlap_ms_{axis}"] = row["overlapped_ms"]
    if shards:
        for row in shards.values():
            for k in list(row):
                if k.endswith("_ms"):
                    row[k] = round(row[k], 3)
        out["shards"] = {k: shards[k] for k in sorted(shards)}
    if tenants:
        for row in tenants.values():
            row["prefill_ms"] = round(row["prefill_ms"], 3)
        out["tenants"] = {k: tenants[k] for k in sorted(tenants)}
    # serving-fault keys ride along only when a fault actually fired
    # (same conditional pattern as "mesh"/"shards"/"tenants")
    if any(faults.values()):
        faults["failover_recovery_ms"] = round(
            faults["failover_recovery_ms"], 3)
        out.update(faults)
    # host-tier spill/promote counts ride along only when the tier
    # actually moved blocks (same conditional pattern as faults)
    if any(hostkv.values()):
        out.update(hostkv)
    # store-outage lane, only when an outage actually happened
    if any(degraded.values()):
        degraded["degraded_ms"] = round(degraded["degraded_ms"], 3)
        out.update(degraded)
    # lazy eager-capture lane, only when segments actually flushed
    if lazy_lane["lazy_flush_count"]:
        lazy_lane["lazy_ms"] = round(lazy_lane["lazy_ms"], 3)
        lazy_lane["segment_cache_hit_rate"] = round(
            lazy_lane["lazy_cache_hits"]
            / lazy_lane["lazy_flush_count"], 4)
        out.update(lazy_lane)
    # elastic-training recovery/snapshot lanes, only when they fired
    if any(elastic.values()):
        elastic["recovery_ms"] = round(elastic["recovery_ms"], 3)
        elastic["ckpt_ms"] = round(elastic["ckpt_ms"], 3)
        out.update(elastic)
    # fabric lane (cross-host KV handoffs), only when transfers ran.
    # hidden ratio = the fraction of transfer time covered by compute
    # dispatch spans (decode steps on the surviving/adopting hosts) —
    # interval intersection, same machinery as collective_overlap_stats
    if any(fabric.values()):
        compute = sorted((e.ts, e.ts + e.dur) for e in events
                         if e.dur is not None
                         and e.cat in ("dispatch", "decode"))
        merged = []
        for a, b in compute:
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        total = covered = 0.0
        for a, b in fabric_spans:
            total += b - a
            hid = sum(max(0.0, min(b, y) - max(a, x))
                      for x, y in merged)
            covered += min(hid, b - a)
        fabric["fabric_hidden_ratio"] = round(covered / total, 4) \
            if total else 0.0
        fabric["fabric_ms"] = round(fabric["fabric_ms"], 3)
        fabric["cluster_failover_ms"] = round(
            fabric["cluster_failover_ms"], 3)
        out.update(fabric)
    return out


def collective_overlap_stats(events=None):
    """Per-axis compute/communication overlap from real timeline spans.

    For every mesh axis that recorded ``cat="collective"`` spans (the
    eager collectives and the overlapped-matmul measured driver both
    stamp ``axis=...``), measures how much of the collective's span was
    covered by compute spans (``cat="dispatch"``) — the
    tile-level overlap actually achieved, not asserted.  Ratio 1.0
    means every byte of collective time ran under compute; ~0 means the
    MXU sat idle for the transfer (the sequential fallback's
    signature).  Returns ``{axis: {collective_ms, overlapped_ms,
    overlap_ratio, count, bytes}}`` — empty when no axis-stamped
    collectives were recorded.
    """
    if events is None:
        events = get_timeline().events()
    compute = sorted((e.ts, e.ts + e.dur) for e in events
                     if e.dur is not None
                     and e.cat == "dispatch")
    merged = []
    for a, b in compute:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    per = {}
    for e in events:
        if e.dur is None or e.cat != "collective":
            continue
        attrs = e.attrs or {}
        axis = attrs.get("axis")
        if not axis:
            continue
        row = per.setdefault(str(axis), {
            "collective_ms": 0.0, "overlapped_ms": 0.0,
            "overlap_ratio": 0.0, "count": 0, "bytes": 0})
        a, b = e.ts, e.ts + e.dur
        covered = sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged)
        row["collective_ms"] += (b - a) * 1e3
        row["overlapped_ms"] += min(covered, b - a) * 1e3
        row["count"] += 1
        row["bytes"] += int(attrs.get("bytes", 0) or 0)
    for row in per.values():
        total = row["collective_ms"]
        row["overlap_ratio"] = round(row["overlapped_ms"] / total, 4) \
            if total else 0.0
        row["collective_ms"] = round(row["collective_ms"], 3)
        row["overlapped_ms"] = round(row["overlapped_ms"], 3)
    return per


def _pipeline_lane_stats(events):
    """Core pipeline sweep over one lane's worth of span events."""
    dispatch = sorted((e.ts, e.ts + e.dur) for e in events
                      if e.dur is not None and e.cat == "dispatch")
    syncs = sorted((e.ts, e.ts + e.dur) for e in events
                   if e.dur is not None and e.cat in ("pipeline", "d2h"))
    h2d = [(e.ts, e.ts + e.dur) for e in events
           if e.dur is not None and e.cat == "h2d"]

    # Under async dispatch the ``dispatch`` span closes when the host
    # enqueue returns, not when the device finishes — so a step is IN
    # FLIGHT from its dispatch start until the sync that retires it
    # (its ``pipeline.wait`` or first ``d2h`` read), matched FIFO.  A
    # dispatch with no later sync falls back to its own span, so a
    # purely synchronous trace never fabricates overlap.
    inflight = []
    si = 0
    for a, b in dispatch:
        while si < len(syncs) and syncs[si][1] < b:
            si += 1
        if si < len(syncs):
            inflight.append((a, max(b, syncs[si][1])))
            si += 1
        else:
            inflight.append((a, b))

    def _overlap(a, b):
        return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))

    total_h2d = sum(b - a for a, b in h2d)
    overlap = 0.0
    for seg in h2d:
        covered = sum(_overlap(seg, d) for d in inflight)
        overlap += min(covered, seg[1] - seg[0])

    # measured depth: sweep starts/ends of the in-flight + h2d lanes
    edges = []
    for a, b in inflight + h2d:
        edges.append((a, 1))
        edges.append((b, -1))
    edges.sort()
    depth = cur = 0
    for _, d in edges:
        cur += d
        depth = max(depth, cur)

    return {
        "h2d_ms": round(total_h2d * 1e3, 3),
        "overlap_ms": round(overlap * 1e3, 3),
        "overlap_ratio": round(overlap / total_h2d, 4) if total_h2d else 0.0,
        "measured_depth": depth,
        "dispatch_count": len(dispatch),
        "h2d_count": len(h2d),
    }


def pipeline_stats(events=None):
    """Measured async-pipeline health from the timeline.

    ``overlap_ms``/``overlap_ratio``: how much of the recorded h2d
    transfer time ran WHILE a step was in flight (dispatched but not
    yet synchronized) — the device prefetch doing its job (1.0 = every
    transfer fully hidden behind compute).  ``measured_depth``: the max
    number of concurrently in-flight steps + open h2d transfers, i.e.
    the pipeline depth the run actually achieved (1 = fully serial).

    Spans stamped with a ``shard`` attr (serving DP engines emit
    ``shard="dp<i>"``) additionally get an independent per-shard sweep
    under ``per_shard[<shard>]`` — in-flight matching happens within
    each shard's own lane so one replica's sync never retires another
    replica's dispatch.  The top-level numbers stay the whole-process
    aggregate and are unchanged for unsharded traces.
    """
    if events is None:
        events = get_timeline().events()
    out = _pipeline_lane_stats(events)
    lanes = {}
    for e in events:
        if e.dur is None:
            continue
        shard = (e.attrs or {}).get("shard")
        if shard:
            lanes.setdefault(str(shard), []).append(e)
    if lanes:
        out["per_shard"] = {k: _pipeline_lane_stats(v)
                            for k, v in sorted(lanes.items())}
    overlap = collective_overlap_stats(events)
    if overlap:
        # per-axis compute/communication overlap next to the h2d
        # pipeline numbers (ISSUE 11: the win is measured, not asserted)
        out["overlap"] = overlap
    return out


def lint_summary_table(events=None, limit=20):
    """Text table of tpu_lint findings recorded on the timeline.

    The analyzers emit each diagnostic as a ``cat="analysis"`` instant
    named ``lint:<code>`` with severity/site/message attrs
    (``paddle_tpu.analysis``); this groups them per code the way
    ``summary()`` groups spans per op.
    """
    if events is None:
        events = get_timeline().events()
    per_code = {}
    for e in events:
        if e.cat != "analysis" or not e.name.startswith("lint:"):
            continue
        code = e.name[len("lint:"):]
        attrs = e.attrs or {}
        rec = per_code.setdefault(
            code, {"count": 0, "severity": attrs.get("severity", "?"),
                   "sites": []})
        rec["count"] += 1
        site = attrs.get("site")
        if site and site not in rec["sites"]:
            rec["sites"].append(site)
    if not per_code:
        return "tpu_lint: no diagnostics recorded"
    lines = [f"{'code':<8} {'sev':<8} {'count':>5}  sites"]
    order = {"error": 0, "warning": 1, "info": 2}
    for code, rec in sorted(
            per_code.items(),
            key=lambda kv: (order.get(kv[1]["severity"], 3),
                            -kv[1]["count"]))[:limit]:
        sites = ", ".join(rec["sites"][:3])
        if len(rec["sites"]) > 3:
            sites += f", +{len(rec['sites']) - 3} more"
        lines.append(f"{code:<8} {rec['severity']:<8} "
                     f"{rec['count']:>5}  {sites}")
    return "\n".join(lines)
