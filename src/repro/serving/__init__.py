"""Serving substrate: PIM weight conversion + fixed-batch and
continuous-batching (paged KV cache) engines, both optionally tensor-sharded
over a 1-D ``"model"`` mesh (``serving.sharded``).

Speculative multi-token decode (``serving.speculative``): pass
``speculate=SpecConfig(k=...)`` (or a bare int ``k``) to
``ServingEngine.generate`` or to the ``ContinuousBatchingEngine``
constructor to amortise each weight stream over up to ``k+1`` emitted
tokens.  Proposals come from prompt-lookup n-grams (``mode="ngram"``,
both engines) or a small draft model (``mode="draft"``, constructed with
``draft_cfg``/``draft_params``; the continuous engine keeps the draft's
state in its own paged pool).  The target verifies the whole window in
one ``models.verify_step`` forward.  Under greedy decode, acceptance is
longest greedy-matching prefix and output stays token-identical to plain
greedy decode; under temperature/top-k sampling, acceptance is rejection
sampling (``serving.sampling.rejection_sample``), which preserves the
plain sampled output distribution exactly, with every draw keyed per
(request, counter) so the same ``key`` gives identical tokens on either
engine and any mesh width.  Realised acceptance lands in
``ServingEngine.spec_stats`` / ``ContinuousBatchingEngine.spec_emitted``
/ ``spec_live_steps``.

Failure semantics (``serving.resilience`` + ``serving.chaos``): the
continuous engine's ``serve_detailed`` accepts a ``ResiliencePolicy``
(per-request deadlines/SLO classes, bounded admission queue with load
shedding, retry-with-backoff for transient chunk faults, a graceful-
degradation ladder, periodic crash-replay snapshots) and a seeded
``FaultInjector`` that makes every failure mode reproducible.  Transient
chunk faults are RETRIED (the failed attempt never ran); expired,
overflowing, or unschedulable requests are SHED (lowest SLO class first,
partial tokens kept); corrupt/invalid payloads are REJECTED at admission;
under sustained pressure service DEGRADES one ladder rung at a time
(shrink the speculative window → disable speculation → halve the chunk →
shed low-SLO queue entries — token-preserving for greedy decode); and
after a crash the ``ServingSupervisor`` (built on
``runtime.fault.HeartbeatMonitor``) restores the last ``ServeSnapshot``
and REPLAYS in-flight requests token-identically — the fold_in
(request, counter) draw keys continue the exact random stream.  See the
``serving.resilience`` module docstring for the full contract.

Shared-prefix KV cache + fleet routing (``serving.prefix`` +
``serving.router``): constructing the continuous engine with
``prefix_cache=True`` deduplicates page-aligned prompt prefixes across
requests — a refcounted ``PagePool`` plus a host-side ``PrefixTrie`` alias
matching read-only pages through the block tables, prefill only the
unmatched tail (one ``models.verify_step`` window), fork copy-on-write
when a write frontier lands inside a shared page, and retain/evict
refcount-0 cached pages LRU under pool pressure.  Cache hits are
token-identical to uncached serving (greedy and sampled).
``ReplicaRouter`` spreads a request stream over N engines (least-loaded
with prefix-affinity), token-identical per request to a solo engine.
Per-request span events land on ``RequestRecord.events`` and export as
deterministic chrome-tracing JSON via ``tools/trace_export.py``."""
from .chaos import (
    ChaosConfig,
    ChunkFault,
    EngineCrash,
    FaultInjector,
    VirtualClock,
)
from .engine import (
    ContinuousBatchingEngine,
    Request,
    ServingEngine,
    init_serving_params,
    mask_after_stop,
    pim_bytes,
    quantize_tree,
)
from .prefix import PagePool, PrefixTrie, chunk_keys, extras_fingerprint
from .resilience import (
    LadderConfig,
    ResiliencePolicy,
    ServeReport,
    ServeSnapshot,
    ServingSupervisor,
    load_snapshot,
    save_snapshot,
)
from .sampling import (
    acceptance_probs,
    draw_keys,
    rejection_sample,
    residual_dist,
    sample_rows,
    warp_logits,
)
from .router import ReplicaRouter, RouterReport
from .sharded import make_decode_mesh, shard_quantized_tree, tree_pspecs
from .speculative import SpecConfig, propose_ngram

__all__ = [
    "ServingEngine", "ContinuousBatchingEngine", "Request", "quantize_tree",
    "init_serving_params", "pim_bytes", "mask_after_stop", "make_decode_mesh",
    "shard_quantized_tree", "tree_pspecs", "SpecConfig", "propose_ngram",
    "acceptance_probs", "residual_dist", "rejection_sample", "sample_rows",
    "warp_logits", "draw_keys",
    "ChaosConfig", "FaultInjector", "ChunkFault", "EngineCrash",
    "VirtualClock", "ResiliencePolicy", "LadderConfig", "ServeReport",
    "ServeSnapshot", "ServingSupervisor", "save_snapshot", "load_snapshot",
    "PagePool", "PrefixTrie", "chunk_keys", "extras_fingerprint",
    "ReplicaRouter", "RouterReport",
]
