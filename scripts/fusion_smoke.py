#!/usr/bin/env python
"""fusion_smoke: probe every gated Pallas kernel in interpret mode.

    python scripts/fusion_smoke.py [--json]

Force-probes each kernel registered with ``pallas_gate`` (flash
attention, ragged attention, layer_norm, layer_norm+residual,
matmul-epilogue, rms_norm, softmax cross-entropy) — fwd AND bwd where
the probe takes a grad — without needing a TPU, then prints the
``probe_report()`` outcome and each probe's wall time (interpret mode
on the CPU: a liveness figure, not a kernel time; kernel time is device
time, read from a profiler trace by kernel name).  Exit code 1 iff any
kernel fails its probe: a red run here means the same kernel would
silently fall back to the XLA composite on hardware.  Runs in the
tier-1 suite via tests/test_analysis.py (``perf`` marker).
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(emit_json=False, out=sys.stdout):
    from paddle_tpu.ops import pallas_gate as pg

    pg.reset_probe_cache()
    timings = {}
    for kernel in pg._PROBES:
        t0 = time.time()
        pg.probe_kernel(kernel, force=True)
        timings[kernel] = round((time.time() - t0) * 1e3, 1)
    report = pg.probe_report()
    pg.reset_probe_cache()

    ok = all(r.get("ok") for r in report.values())
    if emit_json:
        print(json.dumps({"ok": ok, "probes": report,
                          "probe_wall_ms": timings}, indent=2,
                         default=str), file=out)
    else:
        for kernel, rec in report.items():
            status = "OK" if rec.get("ok") else "FAIL"
            line = f"[fusion_smoke] {kernel:<24} {status:<6} " \
                   f"({timings[kernel]:.0f} ms)"
            if not rec.get("ok"):
                line += f"  {rec.get('error', '')[:120]}"
            print(line, file=out)
    return ok, report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="print machine-readable JSON instead of text")
    args = ap.parse_args(argv)
    ok, _ = run(emit_json=args.json)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
