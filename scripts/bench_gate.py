#!/usr/bin/env python
"""bench_gate: perf regression gate between two bench.py captures.

    python scripts/bench_gate.py --last-good PATH
                                 [--threshold 0.05] [--fresh PATH] [--json]

Runs ``bench.py`` in a subprocess for a FRESH capture (or reads one
from ``--fresh``) and compares it with the ``--last-good`` capture on
every shared gated metric: higher-is-better throughput (the headline
plus all ``*_tokens_per_sec`` / ``*_imgs_per_sec`` / ``*_accept_rate``
/ ``*_hidden_ratio`` entries in ``extra_metrics``), lower-is-better
latency (``*_p99_ttft_ms``, ``*_failover_ms``, ...), and zero-tolerance
quality parity (``*_greedy_match``: ANY drop below last-good refuses
the capture).  Exits 1 iff any shared metric regressed by more than
``--threshold`` (default 5%) in its bad direction.

bench.py measures a chip or fails, so every capture is a live device
measurement and every exit path here is a verdict — seed, pass, or
fail.  A capture the gate cannot judge (another platform or device
kind, or no shared gated metrics) fails: silently waving a round
through is how perf regressions land.  With no ``--last-good`` file
yet, the fresh capture SEEDs it (exit 0).
"""
import argparse
import json
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

GATE_SUFFIXES = ("_tokens_per_sec", "_imgs_per_sec", "_accept_rate",
                 "_hit_rate", "_hidden_ratio", "_overlap_ratio")
#: lower-is-better latency metrics: a RISE beyond the threshold fails
#: (note: "_failover_recovery_ms" does NOT match "_failover_ms" — the
#: cluster drill's recovery metric gates separately from the DP one;
#: "_expert_imbalance" is the MoE routing gauge — hotter routing means
#: padded grouped blocks, so a rise gates like a latency regression)
LOW_SUFFIXES = ("_p99_ttft_ms", "_p99_tpot_ms", "_failover_recovery_ms",
                "_shed_rate", "_elastic_recovery_ms", "_failover_ms",
                "_stall_ms", "_expert_imbalance",
                # lazy-tier: more segment flushes per train step means
                # whole-step capture regressed toward per-op dispatch
                "_flushes_per_step")
#: quality-parity metrics (int8 greedy match vs float): ZERO tolerance
#: — ANY drop below last-good refuses the capture, threshold ignored
QUALITY_SUFFIXES = ("_greedy_match",)


def log(msg):
    print(f"[bench_gate] {msg}", file=sys.stderr, flush=True)


def capture_fresh(timeout_s):
    """Run bench.py in a subprocess; its contract is ONE JSON line on
    stdout (diagnostics go to stderr)."""
    cmd = [sys.executable, str(ROOT / "bench.py")]
    log("capturing fresh: " + " ".join(cmd))
    proc = subprocess.run(cmd, cwd=str(ROOT), stdout=subprocess.PIPE,
                          timeout=timeout_s, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"bench.py exited rc={proc.returncode}")
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if not lines:
        raise RuntimeError("bench.py printed no JSON line")
    return json.loads(lines[-1])


def gated_metrics(payload):
    """{name: value} of the headline + throughput/latency extras."""
    out = {}
    if payload.get("metric") and payload.get("value", 0) > 0:
        out[payload["metric"]] = float(payload["value"])
    for name, v in (payload.get("extra_metrics") or {}).items():
        if name.endswith(GATE_SUFFIXES + LOW_SUFFIXES
                         + QUALITY_SUFFIXES) \
                and isinstance(v, (int, float)) and v > 0:
            out[name] = float(v)
    return out


def compare(last_good, fresh, threshold):
    """(regressions, rows) over metrics present in BOTH captures."""
    old = gated_metrics(last_good)
    new = gated_metrics(fresh)
    names = set(old) & set(new)
    rows, regressions = [], []
    for name in sorted(names):
        delta = new[name] / old[name] - 1.0
        verdict = "ok"
        if name.endswith(QUALITY_SUFFIXES):
            # quality parity: any drop below last-good is a refusal
            if new[name] < old[name]:
                verdict = "REGRESSION"
                regressions.append(name)
        else:
            lower_better = name.endswith(LOW_SUFFIXES)
            if (delta > threshold) if lower_better \
                    else (delta < -threshold):
                verdict = "REGRESSION"
                regressions.append(name)
        rows.append({"metric": name, "last_good": old[name],
                     "fresh": new[name], "delta": round(delta, 4),
                     "verdict": verdict})
    return regressions, rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="max tolerated fractional drop (default 0.05)")
    ap.add_argument("--last-good", required=True,
                    help="the capture to judge against (seeded from "
                         "the fresh one when the file does not exist)")
    ap.add_argument("--fresh", default=None,
                    help="use this capture JSON instead of running "
                         "bench.py (testing / re-judging a capture)")
    ap.add_argument("--timeout", type=int, default=5400,
                    help="bench.py subprocess timeout in seconds")
    ap.add_argument("--json", action="store_true",
                    help="print a machine-readable verdict")
    args = ap.parse_args(argv)

    def emit(status, rows=(), note=""):
        if args.json:
            print(json.dumps({"status": status, "note": note,
                              "threshold": args.threshold,
                              "rows": list(rows)}, indent=1))
        else:
            for r in rows:
                print(f"  {r['verdict']:>10}  {r['metric']}: "
                      f"{r['last_good']:,.1f} -> {r['fresh']:,.1f} "
                      f"({r['delta']:+.1%})")
            print(f"bench_gate: {status}" + (f" — {note}" if note else ""))

    last_path = Path(args.last_good)
    last_good = json.loads(last_path.read_text()) \
        if last_path.exists() else None

    if args.fresh:
        fresh = json.loads(Path(args.fresh).read_text())
    else:
        fresh = capture_fresh(args.timeout)
    # every exit path is a verdict — seed, pass, or fail — never a
    # silent wave-through
    if last_good is None:
        try:
            last_path.write_text(json.dumps(fresh, indent=1))
        except Exception as e:
            log(f"seeding last-good failed: {e}")
            emit("FAIL", note=f"no last-good at {last_path} and seeding "
                 f"it from the live capture failed: {e}")
            return 1
        emit("SEEDED", note=f"no last-good artifact existed; live "
             f"capture written to {last_path} — the next live round "
             "is gated against it")
        return 0

    for key in ("platform", "device_kind"):
        if last_good.get(key) != fresh.get(key):
            emit("FAIL", note=f"{key} mismatch: last-good "
                 f"{last_good.get(key)} vs fresh {fresh.get(key)} — "
                 "incomparable captures; re-seed by moving the "
                 "last-good artifact aside")
            return 1

    regressions, rows = compare(last_good, fresh, args.threshold)
    if not rows:
        emit("FAIL", note="live capture shares no gated metrics with "
             "the last-good artifact — a live round may not pass "
             "unjudged; re-seed by moving the last-good artifact aside")
        return 1
    if regressions:
        emit("FAIL", rows, note=f"{len(regressions)} metric(s) dropped "
             f">{args.threshold:.0%} vs "
             f"{last_good.get('git_rev', '?')} "
             f"({last_good.get('captured_at', '?')})")
        return 1
    emit("PASS", rows,
         note=f"no metric dropped >{args.threshold:.0%} vs "
         f"{last_good.get('git_rev', '?')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
