"""paddle_tpu.observability — unified runtime telemetry.

Three pieces (ISSUE 3 tentpole):

  * **metrics registry** (`registry.py`): thread-safe counters / gauges /
    bounded-reservoir histograms, process-wide singleton.
  * **step timeline** (`timeline.py`): spans + instant events with step
    and rank attribution, recorded into a bounded, lockable buffer by
    the static Executor (compile/dispatch), ``jit.to_static``
    (compile/dispatch), eager collectives (duration + bytes), the
    memory guard (preflight estimates, ladder rungs, structured OOMs),
    and the fault-tolerance layer (injections, retries, watchdog
    timeouts).
  * **exporters** (`export.py`): chrome-trace JSON that loads in
    Perfetto (pid/tid = rank/stream lane, compile→dispatch flow
    arrows), an append-only JSONL sink, and text summary tables.

Env knobs: ``PADDLE_TPU_OBS`` (unset/0 → disabled; every probe is one
global read), ``PADDLE_TPU_OBS_DIR`` (export directory),
``PADDLE_TPU_OBS_CAPACITY`` (event-buffer bound, default 65536).
``paddle.profiler`` is a thin shim over this core.

Beside them, ungated (``blocks.py``): **block scopes** (``block(name)``
over the closed vocabulary ``BLOCKS``) that put a model's blocks into a
compiled step's ``op_name`` metadata, and ``program_blocks()``, the map
from each noted step program's instructions to their block, which a
device trace is joined with by instruction name.

Imports nothing from the rest of paddle_tpu, so every layer can
instrument itself without import cycles.
"""
from .timeline import (  # noqa: F401
    _NULL_SPAN, ENV_OBS, ENV_OBS_CAPACITY, ENV_OBS_DIR, Event, Timeline,
    current_step, disable, enable, enabled, enabled_scope, get_timeline,
    instant, next_flow_id, obs_dir, set_step, span, tag,
)
from .registry import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, get_registry,
)
from .blocks import (  # noqa: F401
    BLOCKS, block, name_scope, note_program, parse_hlo_blocks,
    program_blocks, scope_path, write_blocks,
)
from .export import (  # noqa: F401
    CATEGORY_LANES, chrome_trace, collective_overlap_stats,
    export_chrome_trace, export_jsonl, lint_summary_table, load_jsonl,
    phase_breakdown, pipeline_stats, summary,
)

__all__ = [
    "ENV_OBS", "ENV_OBS_DIR", "ENV_OBS_CAPACITY",
    "Event", "Timeline", "get_timeline", "span", "instant", "tag",
    "enabled", "enable", "disable", "enabled_scope",
    "set_step", "current_step", "next_flow_id", "obs_dir",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "CATEGORY_LANES", "chrome_trace", "collective_overlap_stats",
    "export_chrome_trace", "export_jsonl", "lint_summary_table",
    "load_jsonl", "summary", "phase_breakdown", "pipeline_stats",
    "BLOCKS", "block", "name_scope", "scope_path", "note_program",
    "program_blocks", "parse_hlo_blocks", "write_blocks",
]
