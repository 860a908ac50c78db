"""paddle.profiler over the observability core (+ jax.profiler).

Reference parity: `python/paddle/profiler/` (Profiler with CLOSED→WARMUP→
RECORD scheduler, RecordEvent spans, chrome-trace export;
`fluid/platform/profiler/` host+CUPTI tracers) [UNVERIFIED — empty
reference mount].

Rebuilt as a thin shim over ``paddle_tpu.observability`` (ISSUE 3):
``RecordEvent`` is a boundary span of the shared timeline (a timeline
record plus an XLA TraceAnnotation, so the name shows in the device
trace),
``Profiler.step()`` drives timeline step attribution,
``export_chrome_tracing`` serializes a real Perfetto-loadable trace
through the shared exporter, and ``summary()`` renders the shared op
view.  ``jax.profiler.start_trace/stop_trace`` still captures the
XLA/TPU XPlane timeline alongside, per the RECORD schedule.

A Profiler session force-enables collection for its duration (and
restores the prior ``PADDLE_TPU_OBS`` state on stop), so profiling
works without the env var; the session's host events are cleared on
stop after the ``on_trace_ready`` handler has consumed them.
"""
from __future__ import annotations

import glob
import os
import time
from enum import Enum

import jax

from .. import observability as _obs

__all__ = ["Profiler", "RecordEvent", "ProfilerTarget", "ProfilerState",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "SortedKeys", "SummaryView"]


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SortedKeys(Enum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    GPUTotal = 3
    GPUAvg = 4


class SummaryView(Enum):
    OverView = 0
    OpView = 1
    KernelView = 2


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """CLOSED×closed → READY×ready → RECORD×record, cycling; after
    ``repeat`` full cycles (0 = forever) the schedule stays CLOSED."""
    total = closed + ready + record

    def scheduler(step):
        s = step - skip_first
        if s < 0 or total <= 0:
            return ProfilerState.CLOSED
        if repeat and s >= repeat * total:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready handler factory: serialize the session's timeline
    as chrome-trace JSON under ``dir_name`` (Perfetto-loadable, via the
    shared exporter).  The written path is kept on
    ``prof._last_trace_path``."""
    def handler(prof):
        prof._log_dir = dir_name
        name = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}.pt.trace.json")
        prof._last_trace_path = _obs.export_chrome_trace(path)
        return prof._last_trace_path

    return handler


class RecordEvent:
    """A user's boundary span (``observability.span(name,
    boundary=True)``): always an XLA TraceAnnotation, so the name shows
    beside the device timeline of any profiler trace, and a span in the
    shared timeline when the observability gate is on — a Profiler
    session enables it; so does ``PADDLE_TPU_OBS``."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._span = None

    def begin(self):
        self._span = _obs.span(self.name, cat="host", boundary=True)
        self._span.begin()

    def end(self):
        if self._span is not None:
            self._span.end()
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, **kwargs):
        self._scheduler = scheduler if callable(scheduler) else (
            make_scheduler(record=scheduler[1] - scheduler[0],
                           skip_first=scheduler[0])
            if isinstance(scheduler, (tuple, list)) else
            (lambda step: ProfilerState.RECORD))
        self._on_trace_ready = on_trace_ready
        self._step = 0
        self._active = False
        self._log_dir = os.environ.get("PADDLE_TPU_PROFILE_DIR",
                                       "/tmp/paddle_tpu_profile")
        self._last_trace_path = None
        self._timer_only = timer_only
        self._step_times = []
        self._last_step_t = None
        self._prev_obs = None

    def start(self):
        self._prev_obs = _obs.enable(True)
        _obs.set_step(self._step)
        self._last_step_t = time.perf_counter()
        self._maybe_toggle()

    def _stop_trace(self):
        """End the XPlane capture and write ``blocks.json`` beside it:
        ``observability.program_blocks()``, the map from the traced
        programs' instruction names to the model's blocks (the trace
        itself carries the names alone)."""
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        self._active = False
        runs = glob.glob(os.path.join(self._log_dir, "plugins", "profile",
                                      "*"))
        if runs:
            try:
                _obs.write_blocks(os.path.join(
                    max(runs, key=os.path.getmtime), "blocks.json"))
            except OSError:
                pass

    def stop(self):
        if self._active:
            self._stop_trace()
        if self._on_trace_ready:
            self._on_trace_ready(self)
        # the handler has consumed the session's events; release the
        # bounded buffer so back-to-back sessions never accumulate
        _obs.get_timeline().clear()
        if self._prev_obs is not None:
            _obs.enable(self._prev_obs)
            self._prev_obs = None

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        self._step += 1
        _obs.set_step(self._step)
        self._maybe_toggle()

    def step_info(self, unit=None):
        if not self._step_times:
            return ""
        import numpy as np
        arr = np.asarray(self._step_times[-10:])
        return (f"avg step time {arr.mean() * 1000:.2f} ms "
                f"(min {arr.min() * 1000:.2f}, max {arr.max() * 1000:.2f})")

    def _maybe_toggle(self):
        if self._timer_only:
            return
        state = self._scheduler(self._step)
        should_record = state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN)
        if should_record and not self._active:
            try:
                jax.profiler.start_trace(self._log_dir)
                self._active = True
            except Exception:
                pass
        elif not should_record and self._active:
            self._stop_trace()

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        view = "step" if views == SummaryView.OverView else "op"
        lines = [_obs.summary(view=view)]
        # device memory footprint (SURVEY.md:101 allocator stats)
        from ..device import memory_stats
        s = memory_stats()
        if s:
            gb = 2.0 ** 30
            lines.append(
                f"{'HBM in_use / peak (GiB)':<44}"
                f"{s.get('bytes_in_use', 0)/gb:<8.3f}"
                f"{s.get('peak_bytes_in_use', 0)/gb:<12.3f}")
        out = "\n".join(lines)
        print(out)
        return out

    def export(self, path=None, format="json"):
        """Serialize the current timeline (chrome-trace json or jsonl)."""
        if format == "jsonl":
            return _obs.export_jsonl(path, append=False)
        return _obs.export_chrome_trace(path)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def load_profiler_result(filename):
    """Load an exported trace back (chrome-trace json or jsonl)."""
    import json
    try:
        if str(filename).endswith(".jsonl"):
            return _obs.load_jsonl(filename)
        with open(filename) as f:
            return json.load(f)
    except Exception:
        return None
