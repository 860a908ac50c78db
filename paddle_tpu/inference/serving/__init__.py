"""LLM serving: paged KV cache with COW prefix caching and HBM→host-RAM
tiering, chunked-prefill continuous batching, the unified ragged
generation engine, speculative decoding, SLO-aware multi-tenant
scheduling, streaming delivery, serving-tier fault tolerance (replica
health/failover with deterministic replay, decode watchdog, load
shedding), and prefill/decode disaggregation.

The multi-request generation layer over models/gpt.py — see
README.md §"Serving" and §"Serving fault tolerance".  Entry point:
``GenerationEngine`` (one replica) / ``DataParallelEngine`` (a fleet) /
``DisaggregatedEngine`` (role-split prefill + decode engines) /
``ClusterRouter`` (multi-host fabric: wire-format KV handoffs over
``transport``, gossiped prefix routing, preemption-driven
autoscaling — README §"Cluster serving").
"""
from .kv_cache import (ENV_KV_BLOCK_SIZE, ENV_PREFIX_CACHE,
                       RESIDENT_NAME, PagedKVCache, kv_block_size,
                       prefix_cache_enabled)
from .tiering import (ENV_KV_HOST_BUDGET, ENV_KV_TIERING,
                      HandoffPayload, HostKVPool, kv_host_budget,
                      kv_tiering_enabled)
from .attention import (RaggedCacheView, RaggedLayerCache,
                        kv_blocks_gather, kv_blocks_scatter,
                        kv_cache_scatter, ragged_attention)
from .scheduler import (ENV_MAX_BATCH, ENV_PREFILL_CHUNK,
                        AdmissionPolicy, ContinuousBatchingScheduler,
                        PrefillChunk, Request, TokenBudgetPolicy,
                        VictimPolicy, YoungestFirst, max_batch_size,
                        prefill_chunk_size)
from .speculative import (ENV_SPEC_DRAFT, ENV_SPEC_K,
                          DraftModelProposer, DraftWorker,
                          NgramProposer, SpeculativeConfig, spec_draft,
                          spec_k)
from .slo import SLOPolicy, TenantSpec
from .lora import (ENV_LORA_STORE_BUDGET, AdapterStoreFull,
                   LoRAAdapterStore, SegmentAdapterState,
                   attach_lora_sites, convert_to_lora, load_lora_state_dict,
                   lora_state_dict, lora_store_budget, merge_lora,
                   unmerge_lora)
from .streaming import (ENV_STREAM_QUEUE, StreamEvent, TokenStream,
                        stream_queue_depth)
from .errors import (RequestRejected, ServingError, ServingStepTimeout,
                     ServingUnavailable)
from .engine import (ENV_SHED_DEPTH, ENV_STEP_DEADLINE_MS,
                     GenerationEngine, ragged_sample_next)
from .dp import (HEALTHY, PROBATION, UNHEALTHY, DataParallelEngine,
                 ReplicaHealth)
from .disagg import DisaggregatedEngine
from .transport import (WIRE_MAGIC, WIRE_VERSION, Delivery,
                        HandoffEnvelope, LoopbackTransport,
                        PayloadIntegrityError, PayloadVersionError,
                        StoreTransport, TransportError,
                        TransportTimeout, deserialize_handoff,
                        deserialize_request, serialize_handoff,
                        serialize_request)
from .cluster import ClusterRouter, LocalStore

__all__ = [
    "ENV_KV_BLOCK_SIZE", "ENV_PREFIX_CACHE", "RESIDENT_NAME",
    "PagedKVCache", "kv_block_size", "prefix_cache_enabled",
    "ENV_KV_TIERING", "ENV_KV_HOST_BUDGET", "HandoffPayload",
    "HostKVPool", "kv_tiering_enabled", "kv_host_budget",
    "RaggedCacheView", "RaggedLayerCache", "kv_blocks_gather",
    "kv_blocks_scatter", "kv_cache_scatter", "ragged_attention",
    "ENV_MAX_BATCH", "ENV_PREFILL_CHUNK", "ContinuousBatchingScheduler",
    "PrefillChunk", "Request", "max_batch_size", "prefill_chunk_size",
    "AdmissionPolicy", "TokenBudgetPolicy", "VictimPolicy",
    "YoungestFirst",
    "ENV_SPEC_K", "ENV_SPEC_DRAFT", "SpeculativeConfig",
    "NgramProposer", "DraftModelProposer", "DraftWorker", "spec_k",
    "spec_draft",
    "SLOPolicy", "TenantSpec",
    "ENV_LORA_STORE_BUDGET", "AdapterStoreFull", "LoRAAdapterStore",
    "SegmentAdapterState", "attach_lora_sites", "convert_to_lora",
    "load_lora_state_dict", "lora_state_dict", "lora_store_budget",
    "merge_lora", "unmerge_lora",
    "ENV_STREAM_QUEUE", "StreamEvent", "TokenStream",
    "stream_queue_depth",
    "RequestRejected", "ServingError", "ServingStepTimeout",
    "ServingUnavailable",
    "ENV_SHED_DEPTH", "ENV_STEP_DEADLINE_MS",
    "GenerationEngine", "ragged_sample_next",
    "DataParallelEngine", "ReplicaHealth",
    "HEALTHY", "PROBATION", "UNHEALTHY",
    "DisaggregatedEngine",
    "WIRE_MAGIC", "WIRE_VERSION", "Delivery", "HandoffEnvelope",
    "LoopbackTransport", "PayloadIntegrityError", "PayloadVersionError",
    "StoreTransport", "TransportError", "TransportTimeout",
    "deserialize_handoff", "deserialize_request", "serialize_handoff",
    "serialize_request",
    "ClusterRouter", "LocalStore",
]
