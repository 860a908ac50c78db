"""Diagnostic records: the stable currency of the tpu_lint analyzers.

Every analyzer (tiling legality, recompile risk, host-sync, dtype/amp
audit) emits ``Diagnostic`` objects with a stable code (``TPU1xx`` =
Pallas/Mosaic tiling, ``TPU2xx`` = recompile risk, ``TPU3xx`` =
host-device synchronization, ``TPU4xx`` = dtype/precision), a severity,
the site it was found at, and a fix hint.  ``DiagnosticReport`` is the
ordered collection the orchestrators and the CLI consume.

Runtime-emitted diagnostics (a Pallas probe failure diagnosed at
dispatch time, a mismatched collective payload) append to the bounded
process-wide ``DiagnosticLog`` and surface as ``cat="analysis"``
instants on the observability timeline, so fallbacks show up in traces
instead of vanishing.

Import discipline: this module may import only observability (which
itself imports nothing from paddle_tpu) — every layer records into the
log without cycles.
"""
from __future__ import annotations

import json
import threading
from collections import Counter, deque

from .. import observability as obs

__all__ = ["ERROR", "WARNING", "INFO", "SEVERITIES", "CODES",
           "Diagnostic", "DiagnosticReport", "DiagnosticLog",
           "describe_code", "get_log", "record", "reset_log"]

ERROR = "error"
WARNING = "warning"
INFO = "info"
# rank order for --fail-on comparisons (higher = more severe)
SEVERITIES = {INFO: 0, WARNING: 1, ERROR: 2}

# The stable code registry: code -> (title, default severity).  The
# README diagnostic table and the CLI --explain output render from this.
CODES = {
    # -- Pallas / Mosaic tiling legality (TPU1xx) ----------------------
    "TPU101": ("BlockSpec tile below the dtype's minimum sublane×lane "
               "shape ((8,128) f32, (16,128) bf16, (32,128) int8)", ERROR),
    "TPU102": ("grid does not cover the array: a block dim neither "
               "equals nor divides the padded array dim", ERROR),
    "TPU103": ("estimated VMEM working set exceeds the ~16 MB/core "
               "budget", ERROR),
    "TPU104": ("array crossing the pallas_call boundary has rank < 2 "
               "(Mosaic lays out the last two dims)", WARNING),
    "TPU110": ("Pallas kernel failed its probe compile; dispatch falls "
               "back to the XLA composite", WARNING),
    # -- recompile risk (TPU2xx) ---------------------------------------
    "TPU201": ("weak-typed program input (python scalar promotion): "
               "dtype context changes retrace", WARNING),
    "TPU202": ("executable-cache churn from input shape drift: same "
               "program recompiled per shape", WARNING),
    "TPU203": ("python scalar baked into the trace key as a static "
               "constant: every new value recompiles", WARNING),
    "TPU204": ("program structure mutated in place: fingerprint churn "
               "rebuilds the cached executable", WARNING),
    "TPU205": ("lazy segment cache thrash: one op sequence keeps "
               "fingerprinting to new segments instead of replaying a "
               "cached executable", WARNING),
    # -- host synchronization (TPU3xx) ---------------------------------
    "TPU301": ("early fetch read: a d2h sync lands before the next step "
               "is dispatched, serializing the pipeline", WARNING),
    "TPU302": ("per-step host-sync budget exceeded", WARNING),
    # -- dtype / precision (TPU4xx) ------------------------------------
    "TPU401": ("fp32 matmul/conv under bf16 autocast: op escaped the "
               "AMP white list and runs at half MXU rate", WARNING),
    "TPU402": ("float64 value in the program: TPU emulates f64 in "
               "software", WARNING),
    "TPU403": ("collective payload dtype/shape mismatch (or a software-"
               "emulated wide dtype) on the wire", WARNING),
    "TPU404": ("per-channel int8 scale overflow: a quantization scale is "
               "nonfinite, zero, or collapses the channel to a constant",
               WARNING),
    "TPU405": ("int8 matmul lowered onto a plan whose tiles are not "
               "(32, 128)-legal: the int8 operand forces a relayout",
               WARNING),
    # -- SPMD sharding (TPU5xx) ----------------------------------------
    "TPU501": ("parameter matched by no partition rule: silently "
               "replicated on every device of the mesh", WARNING),
    "TPU502": ("large parameter fully replicated under an fsdp/tp "
               "mesh: every device pays its full HBM cost", WARNING),
    "TPU503": ("collective payload dimension not divisible by the mesh "
               "axis size: ragged shards or a padded transfer", WARNING),
    "TPU504": ("hot-path tensor-parallel matmul whose collective cannot "
               "overlap with compute: the MXU idles for the full "
               "transfer", WARNING),
    "TPU505": ("mesh shrink dropped a model-parallel axis to replication: "
               "the surviving devices cannot hold the axis, so its "
               "parameters re-materialize fully replicated", WARNING),
    "TPU506": ("KV handoff payload cannot hide behind the decode window: "
               "the transfer outlasts the decode steps available before "
               "the destination needs the blocks, so decode stalls on "
               "the fabric", WARNING),
    "TPU507": ("expert capacity below the expected peak load: tokens "
               "past slot C of a hot expert are silently dropped by the "
               "capacity router", WARNING),
    "TPU508": ("expert routing imbalance: a hot expert's load is far "
               "above the mean, so dropless grouped blocks pad (wasted "
               "MXU cycles) and capacity routers drop", WARNING),
    "TPU509": ("adapter-store thrash: the live adapter working set "
               "exceeds the HBM slot pool, so the store keeps spilling "
               "and re-promoting adapters on the decode path", WARNING),
    "TPU510": ("LoRA rank below the dtype's minimum sublane tile: the "
               "packed stacks zero-pad every adapter to the tile floor "
               "and the SGMV dots multiply the padding", WARNING),
    # -- fault-site registry (TPU6xx) ----------------------------------
    "TPU601": ("fault-site reference not in the FAULT_SITES registry: "
               "chaos schedules can never reach it, and a typo'd site "
               "silently never fires", ERROR),
    "TPU602": ("registered fault site with no fault_point() "
               "instrumentation anywhere in the tree: schedules list "
               "it but injection can never trigger", WARNING),
}


def describe_code(code):
    """(title, default severity) for a stable code; KeyError if unknown."""
    return CODES[code]


class Diagnostic:
    """One finding: stable code, severity, site, message, fix hint."""

    __slots__ = ("code", "severity", "message", "site", "hint", "data")

    def __init__(self, code, message, *, site="", hint="", severity=None,
                 data=None):
        if code not in CODES:
            raise ValueError(f"unknown diagnostic code {code!r}")
        self.code = code
        self.severity = severity or CODES[code][1]
        if self.severity not in SEVERITIES:
            raise ValueError(f"unknown severity {self.severity!r}")
        self.message = message
        self.site = site
        self.hint = hint
        self.data = dict(data or {})

    def to_dict(self):
        d = {"code": self.code, "severity": self.severity,
             "message": self.message, "site": self.site}
        if self.hint:
            d["hint"] = self.hint
        if self.data:
            d["data"] = self.data
        return d

    def __repr__(self):
        return (f"Diagnostic({self.code} {self.severity} @{self.site}: "
                f"{self.message})")


class DiagnosticReport:
    """Ordered collection of diagnostics with summary/render helpers."""

    def __init__(self, diagnostics=(), label=""):
        self.label = label
        self._diags = list(diagnostics)

    def __iter__(self):
        return iter(self._diags)

    def __len__(self):
        return len(self._diags)

    def __getitem__(self, i):
        return self._diags[i]

    @property
    def diagnostics(self):
        return list(self._diags)

    def add(self, diag):
        self._diags.append(diag)

    def extend(self, diags):
        for d in diags:
            self.add(d)
        return self

    def by_code(self, code):
        return [d for d in self._diags if d.code == code]

    def errors(self):
        return [d for d in self._diags if d.severity == ERROR]

    def warnings(self):
        return [d for d in self._diags if d.severity == WARNING]

    def counts(self):
        """{code: count}, the compact summary `lint_summary` and
        scripts/tpu_lint.py report."""
        return dict(Counter(d.code for d in self._diags))

    def max_severity(self):
        if not self._diags:
            return None
        return max((d.severity for d in self._diags),
                   key=lambda s: SEVERITIES[s])

    def ok(self, fail_on=ERROR):
        """True when no diagnostic reaches the ``fail_on`` severity."""
        if fail_on in (None, "never"):
            return True
        bar = SEVERITIES[fail_on]
        return all(SEVERITIES[d.severity] < bar for d in self._diags)

    def to_json(self):
        return json.dumps({"label": self.label,
                           "diagnostics": [d.to_dict() for d in self]},
                          indent=1)

    def render(self, limit=None):
        """Text table: CODE SEVERITY SITE MESSAGE (+ hint lines)."""
        head = f"== {self.label or 'lint'}: " + (
            "clean" if not self._diags else
            f"{len(self.errors())} error(s), "
            f"{len(self.warnings())} warning(s), "
            f"{len(self._diags)} total")
        lines = [head]
        for d in self._diags[:limit]:
            lines.append(f"  {d.code} [{d.severity:<7}] {d.site}: "
                         f"{d.message}")
            if d.hint:
                lines.append(f"      hint: {d.hint}")
        if limit is not None and len(self._diags) > limit:
            lines.append(f"  ... {len(self._diags) - limit} more")
        return "\n".join(lines)

    def emit(self):
        """Record every diagnostic: bounded process log + obs instant."""
        for d in self._diags:
            record(d)
        return self


class DiagnosticLog:
    """Bounded process-wide log of runtime-emitted diagnostics."""

    def __init__(self, capacity=1024):
        self._lock = threading.Lock()
        self._buf = deque(maxlen=capacity)

    def append(self, diag):
        with self._lock:
            self._buf.append(diag)

    def events(self):
        with self._lock:
            return list(self._buf)

    def counts(self):
        with self._lock:
            return dict(Counter(d.code for d in self._buf))

    def clear(self):
        with self._lock:
            self._buf.clear()


_log = DiagnosticLog()


def get_log():
    """The process-wide diagnostic log (probe fallbacks, runtime checks)."""
    return _log


def reset_log():
    _log.clear()


def record(diag):
    """Append to the process log and mark the observability timeline."""
    _log.append(diag)
    if obs.enabled():
        obs.instant("lint:" + diag.code, cat="analysis",
                    severity=diag.severity, site=diag.site,
                    message=diag.message)
    return diag
