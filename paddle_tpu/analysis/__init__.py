"""tpu_lint: static program analysis for TPU programs.

Inspects programs *before dispatch* and emits structured ``Diagnostic``
records with stable codes (TPU1xx tiling, TPU2xx recompile risk,
TPU3xx host sync, TPU4xx dtype/precision), severity, site and fix
hint.  Entry points:

* ``Executor.analyze_program(...)`` / ``to_static fn.analyze_program()``
  — lint a program/step as it would run;
* ``scripts/tpu_lint.py --models`` — CLI over the bundled models;
* ``analysis.tiling.check_pallas_call`` — validate a kernel block plan
  (``ops/pallas_gate.py`` uses it to diagnose probe failures);
* ``analysis.analyze_runtime()`` — audit the live process (timeline,
  executable caches) after steps ran;
* ``observability.lint_summary_table()`` — render recorded findings.
"""
from . import (diagnostics, dtype_audit, fabric_audit, host_sync,
               lora_audit, moe_audit, recompile, sharding_audit, tiling)
from .diagnostics import (CODES, ERROR, INFO, SEVERITIES, WARNING,
                          Diagnostic, DiagnosticLog, DiagnosticReport,
                          describe_code, get_log, record, reset_log)
from .dtype_audit import audit_jaxpr, check_collective_payload, iter_eqns
from .fabric_audit import audit_fabric_handoff, handoff_bytes_per_block
from .fault_lint import audit_fault_sites, scan_fault_references
from .lora_audit import (audit_adapter_working_set, audit_lora_rank,
                         simulate_adapter_store)
from .moe_audit import audit_expert_capacity, audit_routing_balance
from .host_sync import audit_host_sync, sync_budget
from .sharding_audit import audit_sharding, check_collective_axis
from .program import analyze_runtime, analyze_traced, lint_summary
from .recompile import (audit_eager_cache, audit_executor_cache,
                        audit_trace_cache, audit_weak_types)
from .tiling import (LANE, VMEM_BYTES, audit_flash_attention,
                     audit_grouped_matmul, audit_layer_norm_residual,
                     audit_lora_sgmv, audit_matmul_epilogue,
                     audit_ragged_attention, check_block_spec,
                     check_pallas_call, estimate_vmem_bytes, min_tile)

__all__ = [
    "CODES", "ERROR", "INFO", "LANE", "SEVERITIES", "VMEM_BYTES",
    "WARNING", "Diagnostic", "DiagnosticLog", "DiagnosticReport",
    "analyze_runtime", "analyze_traced", "audit_adapter_working_set",
    "audit_eager_cache",
    "audit_executor_cache", "audit_expert_capacity",
    "audit_fabric_handoff",
    "audit_fault_sites", "audit_flash_attention",
    "audit_grouped_matmul", "audit_host_sync",
    "audit_jaxpr", "audit_layer_norm_residual", "audit_lora_rank",
    "audit_lora_sgmv", "audit_matmul_epilogue",
    "audit_ragged_attention",
    "audit_routing_balance",
    "audit_sharding", "audit_trace_cache", "check_collective_axis",
    "audit_weak_types", "check_block_spec", "check_collective_payload",
    "check_pallas_call", "describe_code", "diagnostics", "dtype_audit",
    "estimate_vmem_bytes", "fabric_audit", "get_log",
    "handoff_bytes_per_block", "host_sync", "iter_eqns",
    "lint_summary", "lora_audit", "min_tile", "moe_audit", "record",
    "recompile",
    "reset_log", "scan_fault_references", "simulate_adapter_store",
    "sync_budget", "tiling",
]
