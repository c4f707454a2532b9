"""Serving: static-batch baseline + continuous-batching serve stack.

engine.py    — ServeEngine (fixed-batch anchor, one-call batched prefill)
               and ContinuousServeEngine (slot-pooled, K-token macro-step
               decode, group-batched prefill, CostEngine-scheduled,
               host-sync/dispatch accounted, fault-tolerant: deadlines,
               preemption, bounded queue, watchdogged retries)
slots.py     — SlotPool: per-slot insert/reset/evict of pooled decode state
               (donated buffers, host occupancy/position mirrors, drain()
               failure-path reset; optional paged KV block tables + jitted
               copy-on-write page duplication)
paging.py    — BlockPool: refcounted fixed-size KV pages + the radix prefix
               trie over full blocks (lookup/insert/LRU-evict/drain; pure
               host-side bookkeeping, zero device syncs)
scheduler.py — Request lifecycle state machine + ServeScheduler (site=serve
               / serve_macro / serve_admit / serve_prefix CostEngine
               decisions: admission, prefill chunk, macro horizon,
               deadline-aware load shedding, prefix-cache reuse)
faults.py    — FaultSpec/FaultInjector (raise | nan | stall) + guarded_call
               (watchdog + bounded retry-with-backoff around device steps)
spans.py     — SpanRecorder: optional in-memory host spans at the engine's
               layer boundaries (admission and macro-step phases), each
               also a profiler TraceAnnotation
frontend/    — multi-process serving front end (DESIGN.md §9): host CPU
               topology discovery + SMT-aware affinity planning, pinned
               intake/emission worker processes over bounded IPC queues
               (the site=serve_ipc cost site), and per-request incremental
               token streams published at macro-step boundaries
"""

from repro.serving.engine import (  # noqa: F401
    ContinuousServeEngine,
    ServeEngine,
    ServeReport,
    emitted_count,
)
from repro.serving.faults import (  # noqa: F401
    FatalFault,
    FaultInjector,
    FaultSpec,
    InjectedFault,
    StepFailed,
    guarded_call,
)
from repro.serving.frontend import (  # noqa: F401
    FrontendConfig,
    FrontendError,
    FrontendStream,
    HostTopology,
    ServingFrontend,
    StreamBroken,
    StreamEvent,
    TokenStream,
)
from repro.serving.paging import (  # noqa: F401
    BlockPool,
    PrefixMatch,
    default_kv_blocks,
)
from repro.serving.scheduler import (  # noqa: F401
    InvalidRequestError,
    Request,
    RequestState,
    ServeScheduler,
    supports_chunked_prefill,
    validate_request,
)
from repro.serving.slots import SlotPool  # noqa: F401
