"""Continuous-batching inference serving for the sharded transformer.

The inference workload layer the training-only reference never had:
an iteration-level scheduler (:class:`ServeEngine`) drives jitted
prefill/decode step functions (:mod:`horovod_tpu.serve.decode`) over a
paged KV cache (:mod:`horovod_tpu.serve.kv_cache`) on the same
``jax.sharding.Mesh`` the trainers use, and reports throughput + tail
latency through :mod:`horovod_tpu.serve.metrics`. Above the single
engine, :mod:`horovod_tpu.serve.router` runs a fleet: N replicas
behind a cache-affinity admission router with prefill/decode pools
(KV handoff) and deadline-class load shedding. The fleet spans
processes: :mod:`horovod_tpu.serve.rpc` lifts the engine seam onto a
length-prefixed RPC framing over the native vectored TCP transport,
:mod:`horovod_tpu.serve.worker` runs one engine per worker process,
and the router drives local and remote replicas identically
(heartbeat liveness, dead-worker requeue, drains that migrate RUNNING
decodes).

Quick start::

    from horovod_tpu.models import TransformerConfig, init_transformer
    from horovod_tpu import serve

    cfg = TransformerConfig.tiny()
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    engine = serve.ServeEngine(cfg, params, serve.ServeConfig(max_batch=8))
    rid = engine.submit(prompt_tokens, max_new_tokens=32)
    while engine.pending:
        engine.step()
    print(engine.result(rid).tokens)

See ``docs/serving.md`` for architecture and tuning.
"""

from horovod_tpu.serve.engine import (  # noqa: F401
    PrefillHandoff,
    QueueFull,
    RequestResult,
    ServeConfig,
    ServeEngine,
)
from horovod_tpu.serve.kv_cache import (  # noqa: F401
    BlockAllocator,
    KVCache,
    NULL_BLOCK,
    OutOfBlocks,
    block_hash,
    hash_chain,
    init_kv_cache,
    pick_bucket,
)
from horovod_tpu.serve.decode import make_serve_fns  # noqa: F401
from horovod_tpu.serve.metrics import ServeMetrics, percentile  # noqa: F401
from horovod_tpu.serve.speculative import (  # noqa: F401
    DraftConfig,
    SpecDecoder,
    accept_greedy,
    make_draft_target_params,
)
from horovod_tpu.serve.router import (  # noqa: F401
    FleetMetrics,
    FleetSaturated,
    RouterConfig,
    ServeRouter,
)
from horovod_tpu.serve.rpc import (  # noqa: F401
    RPC_PROTOCOL_VERSION,
    RemoteReplica,
    RpcConn,
    RpcConnectionError,
    RpcError,
    RpcProtocolError,
    WorkerHandle,
    connect_worker,
    spawn_worker,
)
from horovod_tpu.serve.traces import (  # noqa: F401
    make_multi_tenant_trace,
    make_shared_prefix_trace,
    make_trace,
)
