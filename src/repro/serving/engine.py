"""Serving with PIM-quantized weights: fixed-batch fast path + a
continuous-batching scheduler on a paged KV cache.

``quantize_tree`` converts a trained parameter tree into PIM-mode storage:
every large matmul weight becomes ``{"codes": int8, "scale": f32}`` — the
overlay execution path reads these directly (models.common.linear), cutting
weight HBM traffic 2x vs bf16 / 4x vs f32 at decode time, which is the
memory-bound regime the paper targets (§I: MLP/RNN inference dominated by
memory).  Per-arch quantized-vs-dense logit agreement is tested in
tests/test_serving.py.

Two engines share the model-side decode path:

``ServingEngine.generate`` — ONE lowered XLA program for a fixed batch: a
single-pass prefill over the whole prompt (``models.prefill``) followed by a
``lax.scan`` over the decode steps.  The seed per-token loop survives as
``generate_reference`` — the single parity oracle (prefill AND decode
per-token) and the dispatch-bound baseline in benchmarks/decode_bench.py.
Its weakness is request-level: every sequence rides until the longest one
finishes, and the dense cache preallocates ``B * max_seq`` tokens.

``ContinuousBatchingEngine`` — request-level scheduling on a paged cache:

* **Page / block-table layout** (``models.init_paged_cache``): each layer's
  K/V (or MLA latent) store is a pool of ``num_pages`` fixed-size pages of
  ``page_size`` tokens, shaped ``(P, KV, page_size, D)`` (latents:
  ``(P, page_size, rank)``), shared across all batch slots.  A slot's
  ``block_tables`` row (width ``max_seq / page_size``) maps its logical page
  ``i`` — positions ``[i*page_size, (i+1)*page_size)`` — to a pool page id.
  Decode scatters the new token's K/V through the table and reads the
  slot's pages at the contraction (``models.attention.attn_decode_paged``:
  only the live ones, in place, through ``kernels.paged_attention`` where
  the kernels are on; else a gather of the whole row).
  Page 0 is reserved as the trash page: inactive slots write there, so
  freed pages can be re-issued without cross-slot corruption.  Cache memory
  therefore scales with live tokens (pages in use), not ``B * max_seq``.
  SSM/conv state is O(1) per slot and stays per-slot dense.

* **Scheduler states**: a request is QUEUED until a batch slot and enough
  pages for its (page-aligned) prompt are free; ADMITTED by a batch-1
  single-pass prefill that writes STRAIGHT into its pool pages and per-slot
  state row (``models.prefill`` with ``pages``/``slot``; the old dense
  round-trip survives only as ``models.paged_insert``, the reference for
  the equivalence test) and yields its first token; RUNNING
  while the jit-compiled decode chunk (``lax.scan`` over ``chunk`` steps,
  per-slot ``pos``/``done``/``n_out`` carried) advances all live slots;
  FINISHED when it emits a stop token or reaches ``max_new``, at which
  point its pages return to the free list and the slot admits the next
  queued request — short requests no longer wait on the longest.  If the
  free list runs dry mid-flight the youngest running request is PREEMPTED
  (pages freed, requeued for recompute), matching vLLM-style recompute
  preemption.  The host only intervenes at chunk boundaries (admit /
  page top-up / retire); the inner loop stays one compiled program.

Both engines accept ``mesh=`` (a 1-D ``"model"`` mesh, see
``serving.sharded``): the quantized weight tree is distributed over the
mesh along output dims and every compiled path — the generate scan, the
admit prefill, the decode chunk — lowers ONCE under ``shard_map`` with
weight-stationary local matvecs and a single activation all-gather per
linear.  Host-side scheduling is untouched (it never sees a device count),
and greedy decode stays token-identical to the single-device engines
(tests/test_sharded_decode.py).

Both engines also speculate (``serving.speculative``): ``speculate=`` turns
each decode step into a k-token verify window — one weight stream for up to
k+1 emitted tokens.  Greedy decode stays token-identical to the plain
engines by greedy-prefix acceptance (tests/test_speculative.py); sampled
decode (temperature/top-k) is verified by rejection sampling
(``serving.sampling``), which preserves the plain sampled output
distribution exactly and — because every draw is keyed per (request id,
draw counter) rather than per batch step — emits identical tokens for the
same key on either engine, any mesh width, and across recompute
preemptions (tests/test_sampled_speculative.py).

**Shared-prefix KV page cache** (``prefix_cache=True``,
``serving.prefix``): the block-table indirection already lets several
slots alias ONE pool page, so requests sharing a token-identical prompt
prefix (system prompts, few-shot headers) can share its KV instead of
re-prefilling it.  Lifecycle:

* the page allocator is a refcounted ``prefix.PagePool`` (a page's
  refcount = live block-table references); an uncached admit registers
  its prompt's FULL pages in a ``prefix.PrefixTrie`` keyed by each
  page-aligned chunk's raw token bytes, chained from position 0 under a
  per-extras-fingerprint root — so a page only matches when every
  preceding token and the request's conditioning are identical, exactly
  the causal dependency of its KV content;
* admission probes the trie: matched pages are aliased into the slot's
  block table (refcount + 1) and only the unmatched tail is computed —
  ONE ``models.verify_step`` window at the tail position against the
  aliased prefix (the same per-position math as ``models.prefill``, so
  cache hits stay token-identical to uncached serving, greedy AND
  fold_in-keyed sampled);
* when the tail write frontier lands INSIDE a matched page (a fully
  page-aligned full-prefix hit still recomputes the last position's
  logits, writing its K/V), the page is forked copy-on-write first — a
  writer can never perturb a page a sibling or the trie still reads;
* on retire/preempt the slot's references drop; trie-registered pages
  at refcount 0 are RETAINED on an LRU (``pages_in_use`` counts them as
  reclaimable, not in-use) and re-aliased by later hits, while pool
  pressure (admission, top-up, chaos squeezes) evicts them LRU-first —
  cached pages are opportunistic capacity, never reserved capacity.
  ``assert_quiescent`` accounts for retained pages explicitly.

Only families whose prefill/verify logits agree bitwise are eligible
(``_PREFIX_FAMILIES``): ssm/hybrid carry unpaged per-slot recurrent
state, moe batched expert capacity makes a tail window diverge from a
full prefill under capacity pressure, and MLA's absorbed decode differs
at ~1e-3.  Ineligible families (and draft mode) simply never hit.

**Telemetry** (``RequestRecord.slot``/``.events``, ``ServeReport.counters``
and ``.spans``): every request carries span events —
``{"name", "ts", "dur"?, ...}`` in engine-clock seconds — for admit
(with cached/prefilled token counts), per-round decode (with tokens
emitted), preempt, shed, and finish, plus one per-round counter sample
(free/retained pages, prefix-hit tokens, effective k, queue depth, and the
pages the chunk's last step read, ``kv_pages_read``, against the whole
block-table width of every slot, ``kv_pages_table``).  The
serve loop itself records one host span per phase of each round
(``engine.heartbeat``; ``engine.schedule`` with one nested
``engine.admit`` per admit; then ``engine.idle``, or ``engine.dispatch``,
``engine.wait`` on the chunk's outputs and ``engine.emit``) through one
helper (``_Span``) that writes each span both to ``ServeReport.spans`` and,
over the same interval, to the profiler as a ``TraceAnnotation``, so that
a profiler trace shows the host's phases on the device trace's clock.  The
model names its layers in the compiled programs with ``jax.named_scope``
(``attention``, ``kv_write``, ``linear``, ``lm_head``; ``sample`` and
``nonfinite`` here), which changes HLO metadata only.
``tools/trace_export.py`` turns a report into chrome-tracing JSON (one
Perfetto track per slot, an engine track, counter tracks); under a
``VirtualClock`` the trace is fully deterministic.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
import time
from collections import deque
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import (
    decode_step,
    init_cache,
    init_paged_cache,
    init_params,
    prefill,
    verify_step,
)
from repro.quant import quantize_symmetric
from repro.serving import speculative as spec_mod
from repro.serving.chaos import ChunkFault, EngineCrash
from repro.serving.prefix import (
    PagePool,
    PrefixTrie,
    chunk_keys,
    extras_fingerprint,
)
from repro.serving.resilience import (
    DegradationLadder,
    InflightState,
    LadderConfig,
    RequestRecord,
    ResiliencePolicy,
    ServeReport,
    ServeSnapshot,
)
from repro.serving.sampling import (
    TAG_TOKEN,
    draw_keys,
    sample_rows,
    warp_logits,
)
from repro.serving.sharded import (
    decode_shardings,
    is_qleaf,
    shard_quantized_tree,
    tree_pspecs,
)
from repro.serving.speculative import SpecConfig

# Leaves that stay dense: norms/gains/biases/scalars, router (accuracy-
# critical and tiny), conv kernels, SSM dynamics params.
_DENSE_KEYS = {"ln", "ln1", "ln2", "ln3", "ln_f", "conv_w", "conv_b", "A_log",
               "dt_bias", "D", "router", "gate_attn", "gate_mlp",
               "bq", "bk", "bv", "scale"}

# Metadata leaves — markers, not shipped storage: int4 packing flags and the
# tensor-parallel shard tag added by serving.sharded.shard_quantized_tree.
_MARKER_KEYS = ("nibbles", "nibbles_odd", "tp")

# Families eligible for shared-prefix page caching: those whose admit
# prefill and tail verify_step produce bitwise-identical logits, so a cache
# hit cannot change a single output token.  ssm/hybrid keep per-slot
# recurrent state outside the page pool (nothing to alias); moe expert
# capacity is computed per batched group, so a tail-only window can drop
# tokens a full prefill keeps (see ROADMAP carried-forward note); MLA
# (cfg.mla) absorbed decode differs from expanded prefill at ~1e-3.
_PREFIX_FAMILIES = ("dense", "vlm", "encdec")



def _should_quantize(path, leaf) -> bool:
    names = [str(getattr(p, "key", "")) for p in path]
    if names and names[-1] in _DENSE_KEYS:
        return False
    if leaf.ndim < 2:
        return False
    # embed tables are gathered, not matmul'd — keep dense (tied heads too).
    if names and names[-1] == "embed":
        return False
    return leaf.shape[-1] >= 8 and leaf.shape[-2] >= 8


def _quantize_matrix(w, bits: int) -> dict:
    """One (K, N) weight -> its PIM leaf dict (see ``quantize_tree``)."""
    q = quantize_symmetric(w.astype(jnp.float32), bits=bits, axis=-2)
    if bits != 4:
        return {"codes": q.codes, "scale": q.scale}
    codes = q.codes
    odd = codes.shape[-2] % 2
    if odd:
        codes = jnp.concatenate([codes, jnp.zeros_like(codes[:1])], axis=0)
    lo = codes[0::2] & 0xF
    hi = codes[1::2] & 0xF
    return {"codes": (lo | (hi << 4)).astype(jnp.int8), "scale": q.scale}


def _quantize_leaf(leaf, bits: int) -> dict:
    """Quantize the last two dims of ``leaf``, one matrix at a time over
    any leading stack dims (``lax.map``), so the float32 working copy is
    one layer's matrix, never the whole stack."""
    lead = leaf.shape[:-2]
    if lead:
        flat = leaf.reshape((-1,) + leaf.shape[-2:])
        out = jax.lax.map(lambda w: _quantize_matrix(w, bits), flat)
        out = jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), out)
    else:
        out = _quantize_matrix(leaf, bits)
    if bits == 4:
        marker = "nibbles_odd" if leaf.shape[-2] % 2 else "nibbles"
        out[marker] = jnp.zeros(lead, jnp.int8)
    return out


def quantize_tree(params, bits: int = 8, *, mesh=None):
    """Convert matmul weights to PIM storage. Quantizes the last two dims
    (per-output-channel scales), keeping any leading stack dims.  Leaves
    that are already quantized pass through unchanged.

    bits=4 packs two codes per byte along the K (contraction) dim — the
    storage actually shipped to HBM; ``models.common.linear``/``dq`` unpack
    at the matmul.  An odd K is zero-padded by one code row before packing
    and flagged with the ``nibbles_odd`` marker key so ``dq``/``weight_shape``
    drop the pad row statically (the seed silently fell back to INT8 storage
    for odd K).  The marker leaf ("nibbles" / "nibbles_odd") carries any
    leading stack dims so ``lax.scan`` can slice it.

    ``mesh``: quantize under one ``jit`` whose outputs land directly on
    their decode placement over the mesh (``serving.sharded``), instead of
    on the default device first."""
    if mesh is not None:
        fn = functools.partial(quantize_tree, bits=bits)
        out = decode_shardings(jax.eval_shape(fn, params), mesh)
        return jax.jit(fn, out_shardings=out)(params)

    def conv(path, leaf):
        if is_qleaf(leaf) or not _should_quantize(path, leaf):
            return leaf
        return _quantize_leaf(leaf, bits)

    return jax.tree_util.tree_map_with_path(conv, params, is_leaf=is_qleaf)


def init_serving_params(cfg: ModelConfig, key, *, bits: int, mesh=None):
    """Random PIM-quantized parameters for ``cfg``, equal to
    ``quantize_tree(init_params(cfg, key), bits)`` but built for full
    widths: one ``jit`` draws and quantizes each layer inside the stacked
    init loop (``models.init_params(prepare=)``), so no full-precision
    stack, and no float32 copy of one, ever exists on a device.  With a
    ``mesh`` the outputs land directly on their decode placement and carry
    the ``"tp"`` markers (``serving.sharded``); the random draws are the
    same at any placement."""
    def prepare(tree):
        # The barrier pins each weight at its param dtype before it is
        # quantized: without it the compiler may fuse the draw into the
        # quantizer and skip the rounding to bfloat16 (excess precision).
        return quantize_tree(jax.lax.optimization_barrier(tree), bits)

    def build(k):
        return init_params(cfg, k, prepare=prepare)

    if mesh is None:
        return jax.jit(build)(key)
    out = decode_shardings(jax.eval_shape(build, key), mesh)
    return shard_quantized_tree(jax.jit(build, out_shardings=out)(key), mesh)


def pim_bytes(params, per_device: bool = False) -> int:
    """HBM bytes of a (possibly quantized, possibly sharded) parameter tree.

    The ``nibbles``/``nibbles_odd``/``tp`` leaves are *markers* — metadata
    for ``dq``/``weight_shape``/``linear``, never shipped to HBM — so they
    are excluded from the byte count.

    ``per_device=True`` reports the bytes ONE device actually holds/streams:
    each leaf counts its shard shape under its committed sharding, so a
    mesh-distributed tree reports codes AND scales at 1/devices while
    replicated leaves (norms, markers' siblings, non-divisible weights)
    count in full — instead of silently double-counting replicated storage
    as if it were split.  The default (total) is unchanged: the global
    weight bytes the model streams per token across all devices."""
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if path and str(getattr(path[-1], "key", "")) in _MARKER_KEYS:
            continue
        n = leaf.size
        if per_device:
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None and hasattr(sharding, "shard_shape"):
                n = math.prod(sharding.shard_shape(leaf.shape))
        total += n * leaf.dtype.itemsize
    return total


# ---------------------------------------------------------------- sampling --
def sample_logits(logits, key, *, greedy: bool, temperature, top_k: int):
    """logits (..., V) -> int32 token ids (...): greedy argmax or
    temperature/top-k categorical sampling with ONE key for the whole
    batch.  The engines' decode loops use ``sampling.sample_rows`` with
    per-row counter-derived keys instead (engine-independent streams);
    this stays as the simple one-shot helper."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, warp_logits(logits, temperature, top_k), axis=-1
    ).astype(jnp.int32)


def mask_after_stop(tokens, stop_tokens: Sequence[int], pad_id: int = 0):
    """Replace every token emitted *after* a row's first stop token with
    ``pad_id`` (the stop token itself is kept).  tokens: (B, N) int32."""
    stop_tokens = tuple(stop_tokens)
    if not stop_tokens:
        return tokens
    hit = jnp.zeros(tokens.shape, bool)
    for s in stop_tokens:
        hit = hit | (tokens == s)
    h = hit.astype(jnp.int32)
    stopped_before = (jnp.cumsum(h, axis=1) - h) > 0
    return jnp.where(stopped_before, jnp.int32(pad_id), tokens)


def _generate_body(params, cfg: ModelConfig, prompt, extras, key, temperature,
                   *, n_new: int, max_seq: int, greedy: bool, top_k: int):
    """The whole generation — prefill + n_new decode steps + sampling — as a
    single XLA program (zero per-token Python dispatch).  Jitted directly by
    ``_generate_scan`` or lowered per-device under ``shard_map`` by
    ``_generate_scan_sharded``.  Sampled draws are keyed per row and per
    emission index (``sampling.draw_keys``), so a row's stream is
    independent of batch composition and identical on the paged engine."""
    b, s = prompt.shape
    if n_new == 0:
        return jnp.zeros((b, 0), jnp.int32)
    rids = jnp.arange(b, dtype=jnp.int32)
    cache = init_cache(cfg, b, max_seq)
    logits, cache = prefill(params, cfg, prompt, cache, extras)
    tok0 = sample_rows(
        logits[:, -1, :],
        None if greedy else draw_keys(key, rids, 0, TAG_TOKEN),
        greedy=greedy, temperature=temperature, top_k=top_k)[:, None]

    # Emit AFTER stepping: n_new-1 scan iterations produce tok1..tok_{n-1}
    # (tok0 comes from the prefill logits, draw index 0), so no decode
    # step's output is ever discarded.
    def body(carry, i):
        tok, cache = carry
        lg, cache = decode_step(params, cfg, tok, cache, jnp.int32(s) + i, extras)
        nxt = sample_rows(
            lg[:, -1, :],
            None if greedy else draw_keys(key, rids, i + 1, TAG_TOKEN),
            greedy=greedy, temperature=temperature, top_k=top_k)[:, None]
        return (nxt, cache), nxt[:, 0]

    _, toks = jax.lax.scan(body, (tok0, cache),
                           jnp.arange(n_new - 1, dtype=jnp.int32))
    return jnp.concatenate([tok0, toks.T], axis=1)  # (B, n_new)


_generate_scan = functools.partial(
    jax.jit, static_argnames=("cfg", "n_new", "max_seq", "greedy", "top_k")
)(_generate_body)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "n_new", "max_seq", "greedy", "top_k"),
)
def _generate_scan_sharded(params, cfg: ModelConfig, prompt, extras, key,
                           temperature, *, mesh, n_new: int, max_seq: int,
                           greedy: bool, top_k: int):
    """``_generate_body`` lowered once under ``shard_map``: weights enter
    pre-sharded along their output dims (``tree_pspecs`` reads the ``tp``
    markers), every other operand and every output is replicated — the
    per-layer collectives happen inside ``models.common.linear``/``dq``."""

    def f(p, pr, ex, k, t):
        return _generate_body(p, cfg, pr, ex, k, t, n_new=n_new,
                              max_seq=max_seq, greedy=greedy, top_k=top_k)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(tree_pspecs(params), P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(params, prompt, extras, key, temperature)


class ServingEngine:
    """Fixed-batch engine: single-pass prefill, then a scan-compiled decode
    loop — one XLA program end-to-end.  The baseline the continuous-batching
    engine is benchmarked against (benchmarks/serving_bench.py).

    ``mesh``: a 1-D ``"model"`` mesh (``serving.sharded.make_decode_mesh``)
    distributes the quantized weight tree over its devices; generation then
    runs under ``shard_map`` with per-device weight shards, token-identical
    to the single-device engine."""

    def __init__(self, cfg: ModelConfig, params, max_seq: int,
                 pim_bits: int = 0, mesh=None, draft_cfg: ModelConfig = None,
                 draft_params=None, draft_pim_bits: int = 0):
        self.cfg = cfg
        self.mesh = mesh
        if pim_bits:
            params = quantize_tree(params, pim_bits, mesh=mesh)
        if mesh is not None:
            params = shard_quantized_tree(params, mesh)
        self.params = params
        self.max_seq = max_seq
        # Optional draft model for speculate=SpecConfig(mode="draft"): a
        # smaller same-family model whose k cheap autoregressive steps seed
        # the target's single verify pass.
        self.draft_cfg = draft_cfg
        if draft_params is not None and draft_pim_bits:
            draft_params = quantize_tree(draft_params, draft_pim_bits)
        self.draft_params = draft_params
        self.spec_stats: Optional[dict] = None

    def generate(self, prompt_tokens, n_new: int, extras: Optional[dict] = None,
                 greedy: bool = True, temperature: float = 1.0, top_k: int = 0,
                 key=None, stop_tokens: Sequence[int] = (), pad_id: int = 0,
                 speculate=None):
        """Generate ``n_new`` tokens for the whole batch in one XLA program.

        greedy=True reproduces the seed engine's argmax decoding; for
        dense/SSM/hybrid families the tokens are bit-identical to
        ``generate_reference`` (tests/test_decode_fastpath.py).  MLA archs
        use the absorbed decode form, whose float-association order differs
        from the expanded prefill by ~1e-3 logit units — argmax can flip at
        near-ties (only observable on untrained models, where top-2 margins
        are that small).  greedy=False samples with ``temperature`` and
        optional ``top_k`` filtering, driven by ``key`` (defaults to
        PRNGKey(0) for reproducibility).

        ``stop_tokens`` masks every token a row emits after its first stop
        token with ``pad_id`` (the stop token itself is kept) — pure
        post-processing on the emitted tokens, so varying stop sets never
        recompile the generation program.  The scan still runs ``n_new``
        steps — a fixed batch cannot retire rows early; that is exactly
        what ``ContinuousBatchingEngine`` adds.

        ``speculate`` (a ``serving.SpecConfig`` or an int ``k`` shorthand)
        switches to speculative multi-token decode: propose ``k`` tokens
        (prompt-lookup n-grams, or the engine's draft model), verify them
        with ONE target forward, emit the accepted prefix + one more token
        — the per-token weight stream amortised over the accepted tokens.
        Under greedy decode the output is token-identical to this method's
        plain greedy output (greedy-prefix acceptance); under sampling
        (``greedy=False``) verification is rejection sampling
        (``serving.sampling.rejection_sample``), which leaves the output
        DISTRIBUTION of plain sampled decode exactly unchanged and is
        key-deterministic across engines and meshes (the draws are keyed
        per row and window, not per batch step).  Per-row accepted lengths
        ride in a compiled ``while_loop``.  ``self.spec_stats`` records
        the realised acceptance (``emitted_per_step``) after each
        speculative call."""
        if key is None:
            key = jax.random.PRNGKey(0)
        s = prompt_tokens.shape[1]
        if s + n_new > self.max_seq:
            raise ValueError(
                f"prompt ({s}) + n_new ({n_new}) exceeds max_seq "
                f"({self.max_seq}); cache writes past max_seq would "
                "silently clamp")
        if speculate is not None:
            toks = self._generate_speculative(
                prompt_tokens, int(n_new), extras,
                spec_mod.as_spec(speculate), greedy=bool(greedy),
                temperature=temperature, top_k=int(top_k), key=key)
        elif self.mesh is not None:
            toks = _generate_scan_sharded(
                self.params, self.cfg, prompt_tokens, extras, key,
                jnp.float32(temperature), mesh=self.mesh, n_new=int(n_new),
                max_seq=self.max_seq, greedy=bool(greedy), top_k=int(top_k),
            )
        else:
            toks = _generate_scan(
                self.params, self.cfg, prompt_tokens, extras, key,
                jnp.float32(temperature), n_new=int(n_new), max_seq=self.max_seq,
                greedy=bool(greedy), top_k=int(top_k),
            )
        return mask_after_stop(toks, tuple(stop_tokens), int(pad_id))

    def _generate_speculative(self, prompt_tokens, n_new: int, extras,
                              spec: SpecConfig, *, greedy: bool, temperature,
                              top_k: int, key):
        b = prompt_tokens.shape[0]
        if spec.mode == "draft":
            if self.draft_params is None or self.draft_cfg is None:
                raise ValueError(
                    "speculate mode='draft' needs the engine constructed "
                    "with draft_cfg/draft_params")
            if self.mesh is not None:
                raise NotImplementedError(
                    "draft-model speculation is single-device (the draft "
                    "tree is not mesh-distributed); use mode='ngram' on a "
                    "mesh")
        if spec.tree_fan:
            if self.mesh is not None:
                toks, steps, live_steps = spec_mod._spec_tree_generate_sharded(
                    self.params, self.cfg, prompt_tokens, extras, key,
                    jnp.float32(temperature), mesh=self.mesh, n_new=n_new,
                    max_seq=self.max_seq, fan=spec.tree_fan, depth=spec.k,
                    ngram_n=spec.ngram_n, greedy=greedy, top_k=top_k)
            else:
                toks, steps, live_steps = spec_mod._spec_tree_generate(
                    self.params, self.cfg, prompt_tokens, extras, key,
                    jnp.float32(temperature), n_new=n_new,
                    max_seq=self.max_seq, fan=spec.tree_fan, depth=spec.k,
                    ngram_n=spec.ngram_n, greedy=greedy, top_k=top_k)
        elif self.mesh is not None:
            toks, steps, live_steps = spec_mod._spec_generate_sharded(
                self.params, self.cfg, prompt_tokens, extras, key,
                jnp.float32(temperature), mesh=self.mesh, n_new=n_new,
                max_seq=self.max_seq, k=spec.k, ngram_n=spec.ngram_n,
                greedy=greedy, top_k=top_k, adaptive=spec.adaptive,
                ctrl_alpha=spec.ctrl_alpha, ctrl_init=spec.ctrl_init,
                ctrl_cost=spec.ctrl_cost, accept=spec.accept,
                typical_eps=spec.typical_eps,
                typical_delta=spec.typical_delta)
        else:
            toks, steps, live_steps = spec_mod._spec_generate(
                self.params, self.cfg, prompt_tokens, extras,
                self.draft_params if spec.mode == "draft" else None,
                key, jnp.float32(temperature),
                draft_cfg=self.draft_cfg if spec.mode == "draft" else None,
                n_new=n_new, max_seq=self.max_seq, k=spec.k, mode=spec.mode,
                ngram_n=spec.ngram_n, greedy=greedy, top_k=top_k,
                adaptive=spec.adaptive, ctrl_alpha=spec.ctrl_alpha,
                ctrl_init=spec.ctrl_init, ctrl_cost=spec.ctrl_cost,
                accept=spec.accept, typical_eps=spec.typical_eps,
                typical_delta=spec.typical_delta)
        steps, live_steps = int(steps), int(live_steps)
        # One verify step streams the weight tree once for the WHOLE batch,
        # so the weight-stream amortisation is per-row tokens over verify
        # steps: plain greedy needs n_new-1 streams, speculation `steps`.
        # (Normalising by live_row_steps instead would overstate the win
        # when rows finish at different times — a straggler row keeps the
        # batch streaming.)  acceptance_per_live_row is the per-row window
        # acceptance, the proposer-quality number.
        self.spec_stats = {
            "k": spec.k, "mode": spec.mode, "greedy": greedy,
            "adaptive": spec.adaptive, "tree_fan": spec.tree_fan,
            "accept": spec.accept,
            "verify_steps": steps,
            "live_row_steps": live_steps,
            "emitted_per_step": ((n_new - 1) / steps if steps else 0.0),
            "acceptance_per_live_row": (b * (n_new - 1) / live_steps
                                        if live_steps else 0.0),
        }
        return toks

    def generate_reference(self, prompt_tokens, n_new: int,
                           extras: Optional[dict] = None, greedy: bool = True,
                           temperature: float = 1.0, top_k: int = 0, key=None,
                           stop_tokens: Sequence[int] = (), pad_id: int = 0):
        """The seed per-token loop: one Python dispatch per prompt AND per
        generated token.  THE parity oracle — it exercises both the
        per-token prefill path and the per-token decode path that the
        scan-compiled ``generate`` replaces — and the dispatch-bound
        baseline in decode_bench.  Mirrors ``generate``'s sampling options
        and per-row ``(key, row, draw index)`` key derivation, so matching
        keys give matching samples."""
        if self.mesh is not None:
            raise NotImplementedError(
                "generate_reference is the single-device parity oracle; "
                "construct the engine without a mesh to run it")
        if key is None:
            key = jax.random.PRNGKey(0)
        cfg = self.cfg
        b, s = prompt_tokens.shape
        if n_new == 0:
            return jnp.zeros((b, 0), jnp.int32)
        cache = init_cache(cfg, b, self.max_seq)

        step_fn = jax.jit(
            lambda p, t, c, pos: decode_step(p, cfg, t, c, pos, extras)
        )
        rids = jnp.arange(b, dtype=jnp.int32)

        def draw(logits, idx):
            return sample_rows(
                logits[:, -1, :],
                None if greedy else draw_keys(key, rids, idx, TAG_TOKEN),
                greedy=greedy, temperature=jnp.float32(temperature),
                top_k=int(top_k))[:, None]

        logits = None
        for i in range(s):
            logits, cache = step_fn(self.params, prompt_tokens[:, i : i + 1],
                                    cache, jnp.int32(i))
        tok = draw(logits, 0)
        out = [tok]
        for j in range(n_new - 1):
            logits, cache = step_fn(self.params, tok, cache, jnp.int32(s + j))
            tok = draw(logits, j + 1)
            out.append(tok)
        toks = jnp.concatenate(out, axis=1)
        return mask_after_stop(toks, tuple(stop_tokens), int(pad_id))


# ===================================================== continuous batching ==
@dataclasses.dataclass
class Request:
    """One generation request for ``ContinuousBatchingEngine.serve``.

    ``extras`` are this request's per-slot model inputs (vlm image embeds,
    encdec encoder output) WITHOUT a batch dim; every request in a trace
    must share the same extras structure/shapes (or all pass None).

    The SLO fields only matter under a ``ResiliencePolicy``
    (``serve_detailed``): ``arrival`` is when the request becomes
    admissible and ``deadline`` when its answer stops being useful, both
    in engine-clock seconds from serve start; ``slo`` is the priority
    class load-shedding protects (HIGHER sheds LAST).

    ``rid`` overrides the sampled-draw key id (defaults to the request's
    index in the trace).  Every sampled draw is keyed by (rid, counter),
    so a front-end that splits one logical trace across engine replicas
    (``serving.router``) pins each request's GLOBAL index here and every
    replica emits exactly the tokens a solo engine would."""

    prompt: np.ndarray  # (len,) int32 token ids
    max_new: int  # emit at most this many tokens (>= 1)
    stop_tokens: tuple = ()  # retire early after emitting any of these
    extras: Optional[dict] = None
    arrival: float = 0.0           # not admitted before this engine time
    deadline: Optional[float] = None  # shed from queue / flag miss past this
    slo: int = 1                   # shed priority class (lower sheds first)
    rid: Optional[int] = None      # sampled-draw key id (default: trace index)


class _Span:
    """One host span of the serve loop, recorded twice over one interval:
    as ``{"name", "ts", "dur", **args}`` on a span list (engine clock, as
    ``RequestRecord.events``) and as a ``jax.profiler.TraceAnnotation``
    with the same args, which lands on the profiler's host plane when a
    trace is being taken (about 2 us a span when none is).  A context
    manager, or ``close()`` where the span ends inside a block; args known
    only at the end go in ``late`` before it closes."""

    def __init__(self, spans: list, now, name: str, **args):
        self._spans, self._now = spans, now
        self.name, self.args, self.late = name, args, {}
        self.ts = now()
        self._ann = jax.profiler.TraceAnnotation(name, **args)
        self._ann.__enter__()

    def close(self) -> None:
        if self.late:
            self._ann.set_metadata(**self.late)
        self._ann.__exit__(None, None, None)
        self._spans.append({"name": self.name, "ts": self.ts,
                            "dur": self._now() - self.ts, **self.args,
                            **self.late})

    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _nonfinite_rows(logits, live=True):
    """How many (live) rows of ``logits`` (..., V) hold a NaN or Inf."""
    with jax.named_scope("nonfinite"):
        bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
        return jnp.sum(bad & live, dtype=jnp.int32)


def _admit_body(params, cfg: ModelConfig, cache, prompt, length, slot, pages,
                rid, key, temperature, extras, *, greedy: bool, top_k: int):
    """Admit one request: batch-1 single-pass prefill written STRAIGHT into
    the slot's pool pages and per-slot state row (``models.prefill`` with
    ``pages``/``slot`` — no temporary dense cache, no ``paged_insert``
    scatter round-trip), then sample the first token from the logits at the
    true prompt end with the request's draw-0 key (``key`` is the serve
    call's BASE key; recompute preemption re-derives the same key and
    replays the same token).  Compiled once per padded prompt length (a
    page multiple, carried by ``prompt``'s shape).  Also returns 1 when
    the sampled logits row holds a NaN or Inf, else 0."""
    logits, cache = prefill(params, cfg, prompt, cache, extras, length=length,
                            pages=pages, slot=slot)
    lg = jax.lax.dynamic_index_in_dim(logits, length - 1, axis=1,
                                      keepdims=False)  # (1, V)
    with jax.named_scope("sample"):
        tok0 = sample_rows(
            lg, None if greedy else draw_keys(key, rid[None], 0, TAG_TOKEN),
            greedy=greedy, temperature=temperature, top_k=top_k)[0]
    return cache, tok0, _nonfinite_rows(lg)


_admit_prefill = functools.partial(
    jax.jit, static_argnames=("cfg", "greedy", "top_k"),
    donate_argnames=("cache",),
)(_admit_body)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "greedy", "top_k"),
    donate_argnames=("cache",),
)
def _admit_prefill_sharded(params, cfg: ModelConfig, cache, prompt, length,
                           slot, pages, rid, key, temperature, extras, *,
                           mesh, greedy: bool, top_k: int):
    """``_admit_body`` under ``shard_map``: sharded weights, replicated
    paged cache / prompt / scheduler scalars."""

    def f(p, c, pr, ln, sl, pg, ri, k, t, ex):
        return _admit_body(p, cfg, c, pr, ln, sl, pg, ri, k, t, ex,
                           greedy=greedy, top_k=top_k)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(tree_pspecs(params),) + (P(),) * 9,
        out_specs=P(), check_vma=False,
    )(params, cache, prompt, length, slot, pages, rid, key, temperature,
      extras)


def _pool_leaf_paths(cfg: ModelConfig) -> tuple:
    """(cache key, leading stack dims) of every page-pool subtree for the
    family — the leaves a copy-on-write page fork must duplicate.  Every
    pool leaf (K/V, quantized codes + scales, MLA latents) carries its
    page axis immediately after the lead dims."""
    fam = cfg.family
    if fam == "dense":
        return (("layers", 1),)
    if fam == "moe":
        return ((("layers", 1), ("dense_layers", 1))
                if cfg.n_dense_layers else (("layers", 1),))
    if fam == "vlm":
        return (("groups_self", 2),)
    if fam == "encdec":
        return (("decoder", 1),)
    if fam == "hybrid":
        return (("groups_attn", 1),)
    return ()  # ssm: per-slot state only, nothing paged


@functools.partial(jax.jit, static_argnames=("keys",),
                   donate_argnames=("cache",))
def _copy_page(cache, src, dst, *, keys):
    """Device-side copy-on-write fork: duplicate pool page ``src`` into
    ``dst`` across every paged leaf (``keys`` from ``_pool_leaf_paths``)."""
    new = dict(cache)
    for key, lead in keys:
        idx = (slice(None),) * lead
        new[key] = jax.tree.map(
            lambda l: l.at[idx + (dst,)].set(l[idx + (src,)]), cache[key])
    return new


def _tail_verify_body(params, cfg: ModelConfig, cache, tokens, pos, slot,
                      rid, sample_at, key, temperature, extras, *,
                      greedy: bool, top_k: int, page_size: int):
    """Cached-admit tail: run ONE ``models.verify_step`` window over the
    unmatched tail of a prompt whose prefix pages were aliased from the
    trie.  ``tokens`` (B, T) is zero except the admitted slot's row (the
    padded tail), ``pos`` is zero except ``pos[slot] = tail start``; every
    other row's block-table row is zeroed by the caller, so their window
    writes land in the trash page.  Per window position the math matches
    ``models.prefill``/``decode_step`` exactly (same projections, masks,
    float association — the bit-identity the prefix cache's token-identity
    bar rests on), and the first token is sampled from the logits at the
    true prompt end with the request's draw-0 key, exactly like
    ``_admit_body``."""
    logits, cache = verify_step(params, cfg, tokens, cache, pos, extras,
                                page_size=page_size)
    lg = jax.lax.dynamic_index_in_dim(logits, slot, axis=0, keepdims=False)
    lg = jax.lax.dynamic_index_in_dim(lg, sample_at, axis=0,
                                      keepdims=False)  # (V,)
    tok0 = sample_rows(
        lg[None], None if greedy else draw_keys(key, rid[None], 0, TAG_TOKEN),
        greedy=greedy, temperature=temperature, top_k=top_k)[0]
    return cache, tok0


_tail_verify = functools.partial(
    jax.jit, static_argnames=("cfg", "greedy", "top_k", "page_size"),
    donate_argnames=("cache",),
)(_tail_verify_body)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "greedy", "top_k", "page_size"),
    donate_argnames=("cache",),
)
def _tail_verify_sharded(params, cfg: ModelConfig, cache, tokens, pos, slot,
                         rid, sample_at, key, temperature, extras, *, mesh,
                         greedy: bool, top_k: int, page_size: int):
    """``_tail_verify_body`` under ``shard_map``: sharded weights,
    replicated cache/window operands."""

    def f(p, c, tk, ps_, sl, ri, sa, k, t, ex):
        return _tail_verify_body(p, cfg, c, tk, ps_, sl, ri, sa, k, t, ex,
                                 greedy=greedy, top_k=top_k,
                                 page_size=page_size)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(tree_pspecs(params),) + (P(),) * 10,
        out_specs=P(), check_vma=False,
    )(params, cache, tokens, pos, slot, rid, sample_at, key, temperature,
      extras)


def _decode_chunk_body(params, cfg: ModelConfig, cache, tok, pos, n_out, done,
                       rids, max_new, stops, key, temperature, extras, *,
                       chunk: int, page_size: int, greedy: bool, top_k: int,
                       pad_id: int):
    """``chunk`` decode steps over all batch slots as one compiled scan.

    Per-slot carry: current token, position (cached length), emitted count,
    and done flag.  Done/inactive slots keep stepping (their writes land in
    their own pages or the trash page — harmless) but their emissions are
    masked; the host retires/admits at the chunk boundary.  Sampled draws
    are keyed per slot by ``(key, rid, n_out)`` — the same stream the
    fixed-batch engine consumes — so a request's tokens never depend on
    slot assignment or chunk boundaries.  Also returns how many live
    (slot, step) logits rows held a NaN or Inf."""

    def body(carry, _):
        tok, cache, pos, n_out, done, bad = carry
        lg, cache = decode_step(params, cfg, tok, cache, pos, extras,
                                page_size=page_size)
        live = ~done
        bad = bad + _nonfinite_rows(lg[:, -1, :], live)
        with jax.named_scope("sample"):
            nxt = sample_rows(
                lg[:, -1, :],
                None if greedy else draw_keys(key, rids, n_out, TAG_TOKEN),
                greedy=greedy, temperature=temperature, top_k=top_k)
            emit = jnp.where(live, nxt, jnp.int32(pad_id))
            pos = jnp.where(live, pos + 1, pos)
            n_out = jnp.where(live, n_out + 1, n_out)
            hit = jnp.any(emit[:, None] == stops, axis=1)
            done = done | (live & hit) | (n_out >= max_new)
        return (emit[:, None], cache, pos, n_out, done, bad), (emit, live)

    carry, (emits, lives) = jax.lax.scan(
        body, (tok, cache, pos, n_out, done, jnp.int32(0)), None,
        length=chunk)
    tok, cache, pos, n_out, done, bad = carry
    return cache, tok, pos, n_out, done, emits, lives, bad


_decode_chunk = functools.partial(
    jax.jit,
    static_argnames=("cfg", "chunk", "page_size", "greedy", "top_k", "pad_id"),
    donate_argnames=("cache",),
)(_decode_chunk_body)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "chunk", "page_size", "greedy", "top_k",
                     "pad_id"),
    donate_argnames=("cache",),
)
def _decode_chunk_sharded(params, cfg: ModelConfig, cache, tok, pos, n_out,
                          done, rids, max_new, stops, key, temperature,
                          extras, *, mesh, chunk: int, page_size: int,
                          greedy: bool, top_k: int, pad_id: int):
    """``_decode_chunk_body`` under ``shard_map``: the paged pools, block
    tables, and per-slot scheduler carry are replicated (they are tiny next
    to the weight stream); only the weight shards differ per device."""

    def f(p, c, tk, ps_, no, dn, ri, mn, st, k, t, ex):
        return _decode_chunk_body(p, cfg, c, tk, ps_, no, dn, ri, mn, st, k,
                                  t, ex, chunk=chunk, page_size=page_size,
                                  greedy=greedy, top_k=top_k, pad_id=pad_id)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(tree_pspecs(params),) + (P(),) * 11,
        out_specs=P(), check_vma=False,
    )(params, cache, tok, pos, n_out, done, rids, max_new, stops, key,
      temperature, extras)


class ContinuousBatchingEngine:
    """Continuous-batching scheduler over a paged KV cache (see module
    docstring for the page/block-table layout and scheduler states).

    ``slots`` is the decode batch width; ``num_pages`` bounds total cache
    memory (pages are ``page_size`` tokens each, page 0 is the trash page);
    ``max_seq`` caps a single request's ``prompt + max_new``; ``chunk`` is
    how many decode steps run per compiled program between host scheduling
    points.  Per-request model inputs (vlm image embeds, encdec encoder
    output) ride on ``Request.extras``: admit writes them into the
    request's slot row of a per-slot device buffer, so a request keeps its
    own conditioning no matter which slot it lands in.

    ``page_alloc_seed`` shuffles the free list so block tables become random
    permutations of physical pages — decode must be layout-independent
    (tests/test_paged_serving.py exercises this).

    ``speculate`` (``serving.SpecConfig`` or int ``k``) turns each
    decode-chunk iteration into a speculative verify window: every slot
    proposes ``k`` tokens (its own history via the n-gram proposer, or the
    engine's draft model), the target verifies the window in one pass, and
    each slot advances by its own accepted length — per-slot position/page
    advance stays exact because rejected page writes are dead by masking
    and rewritten by the next window (``models.verify_step``).  Greedy
    output tokens are identical to the non-speculative engine; sampled
    output (``serve(greedy=False)``) is rejection-sampling verified —
    distributionally identical to plain sampled decode and
    key-deterministic per request (``serving.sampling``).
    ``mode="draft"`` (constructed with ``draft_cfg``/``draft_params``)
    keeps the draft model's state in its OWN paged cache pool sharing the
    target's block tables, so draft speculation survives admit/retire and
    recompute preemption like any other per-slot state.  After ``serve``,
    ``spec_emitted / decode_chunk_iters`` is the realised weight-stream
    amortisation (chunk iterations = streams paid, counted for the plain
    engine too so the two are comparable) and
    ``spec_emitted / spec_live_steps`` the per-slot window acceptance."""

    def __init__(self, cfg: ModelConfig, params, *, slots: int, max_seq: int,
                 page_size: int = 8, num_pages: Optional[int] = None,
                 chunk: int = 8, pim_bits: int = 0, pad_id: int = 0,
                 page_alloc_seed: Optional[int] = None, mesh=None,
                 speculate=None, draft_cfg: ModelConfig = None,
                 draft_params=None, draft_pim_bits: int = 0, clock=None,
                 prefix_cache: bool = False):
        self.cfg = cfg
        self.mesh = mesh
        # ``clock``: a 0-arg monotonic-seconds callable (time.monotonic by
        # default; chaos.VirtualClock in tests) — drives request timing,
        # deadlines, and retry backoff in ``serve_detailed``.
        self._clock = clock if clock is not None else time.monotonic
        self.last_snapshot = None  # latest ServeSnapshot (crash recovery)
        self.last_round = -1
        self.last_report = None
        self.spec = None if speculate is None else spec_mod.as_spec(speculate)
        if self.spec is not None and self.spec.mode == "draft":
            if draft_params is None or draft_cfg is None:
                raise ValueError(
                    "speculate mode='draft' needs the engine constructed "
                    "with draft_cfg/draft_params")
            if mesh is not None:
                raise NotImplementedError(
                    "draft-model speculation is single-device (the draft "
                    "tree is not mesh-distributed); use mode='ngram' on a "
                    "mesh")
        if pim_bits:
            params = quantize_tree(params, pim_bits, mesh=mesh)
        if mesh is not None:
            params = shard_quantized_tree(params, mesh)
        self.params = params
        self.draft_cfg = draft_cfg
        if draft_params is not None and draft_pim_bits:
            draft_params = quantize_tree(draft_params, draft_pim_bits)
        self.draft_params = draft_params
        self._draft_mode = (self.spec is not None
                            and self.spec.mode == "draft")
        self.slots = int(slots)
        self.page_size = int(page_size)
        self.max_seq = -(-int(max_seq) // self.page_size) * self.page_size
        # Draft mode: the draft chain READS back the speculative positions
        # it just wrote (the target only writes them), so both pools carry
        # k extra provisioned positions past the request frontier — even a
        # request using the full max_seq budget must never route a draft
        # read through the shared trash page, or cross-engine
        # key-determinism breaks at the boundary.  Tree mode needs a
        # bigger reserve, fan*k: ``models.tree_relocate`` GATHERS the
        # accepted chain's rows from their tree columns (up to
        # pos + fan*k) before scattering them into the linear layout, and
        # a gather through the trash page would corrupt committed
        # positions, not merely waste a proposal.
        if self._draft_mode:
            reserve = self.spec.k
        elif self.spec is not None and self.spec.tree_fan:
            reserve = self.spec.tree_fan * self.spec.k
        else:
            reserve = 0
        self._store_seq = self.max_seq + (
            -(-reserve // self.page_size) * self.page_size)
        self.width = self._store_seq // self.page_size
        if num_pages is None:
            num_pages = self.slots * self.width + 1  # worst case + trash page
        self.num_pages = int(num_pages)
        self.chunk = int(chunk)
        self.pad_id = int(pad_id)
        self._rng = (np.random.default_rng(page_alloc_seed)
                     if page_alloc_seed is not None else None)
        # Shared-prefix page cache (module docstring): only families whose
        # prefill/verify logits agree bitwise are eligible, and the draft
        # pool has no trie (its pages would alias stale draft KV), so
        # ineligible configurations silently never hit.
        self.prefix_cache = bool(prefix_cache)
        self._prefix_on = (self.prefix_cache
                           and cfg.family in _PREFIX_FAMILIES
                           and not getattr(cfg, "mla", None)
                           and not self._draft_mode)
        # Strict pending sweep (serve_detailed): None defers to the
        # REPRO_STRICT_SERVE env var (tests set it); an explicit bool wins.
        self.strict_pending: Optional[bool] = None
        # Test hook: request indices the admission loop silently drops —
        # simulates a scheduler bug so the strict sweep's detection is
        # itself testable.
        self._debug_drop_rids: set[int] = set()
        self._pool_poisoned = False
        # Telemetry plumbing for helpers that fire outside the serve loop's
        # lexical scope (_preempt_slot/_shed): the live RequestRecord
        # list and the engine-clock closure of the current serve call.
        self._records = None
        self._now = lambda: 0.0
        self.prefix_hits = 0        # cached admits (>= 1 page aliased)
        self.prefix_hit_tokens = 0  # prompt tokens served from aliased pages
        self.prefill_tokens = 0     # prompt tokens actually computed
        self.cow_forks = 0          # copy-on-write page forks
        # logits rows holding a NaN or Inf, over admit prefills and plain
        # decode-chunk steps of live slots; 0 for a healthy model
        self.nonfinite_logits = 0
        self.peak_pages_in_use = 0
        self.preemptions = 0
        self.spec_emitted = 0  # tokens emitted by speculative verify windows
        self.spec_live_steps = 0  # live (slot, iteration) verify windows
        # chunk iterations executed, speculative or not — each streams the
        # weight tree once (idle iterations after every slot finishes
        # mid-chunk still pay): chunk-emitted tokens / decode_chunk_iters
        # is the realised weight-stream amortisation, comparable between
        # the plain and speculative engines; spec_emitted/spec_live_steps
        # is the per-slot window acceptance (proposer quality).
        self.decode_chunk_iters = 0
        # Debug invariant (enabled by tests): after every speculative
        # chunk, each live slot's n-gram history row must equal its
        # admitted prompt followed by every emitted token — across ladder
        # no_spec rounds, recompute preemption, and crash-replay resume.
        self.debug_check_hist = False

    # ------------------------------------------------------------- helpers --
    def _spad(self, length: int) -> int:
        """Prompt length padded up to a whole number of pages."""
        ps = self.page_size
        return max(ps, -(-length // ps) * ps)

    def _set_slot_extras(self, slot: int, extras: Optional[dict]):
        """Write a request's extras into its slot row of the per-slot
        buffer the decode chunk reads; returns the batch-1 view for the
        admit prefill."""
        if extras is None:
            return None
        ex = jax.tree.map(jnp.asarray, extras)
        if self._extras_slots is None:
            self._extras_slots = jax.tree.map(
                lambda v: jnp.zeros((self.slots,) + v.shape, v.dtype), ex)
        self._extras_slots = jax.tree.map(
            lambda buf, v: buf.at[slot].set(v), self._extras_slots, ex)
        return jax.tree.map(lambda v: v[None], ex)

    def pages_in_use(self) -> int:
        """Pages with live block-table references.  Retained prefix-cache
        pages (refcount 0, evictable on demand) count as NOT in use —
        they are reclaimable capacity, exactly like free pages."""
        return self._pool.in_use()

    def _alloc_pages(self, n: int) -> list[int]:
        return self._pool.alloc(n)

    def _free_pages(self, pages: list[int]) -> None:
        for p in pages:
            self._pool.release(p)

    def assert_quiescent(self) -> None:
        """Page-pool invariant at quiescence (no live slots): every page
        holds zero references and sits on the free list or the retained
        prefix-cache LRU exactly once.  ``serve_detailed`` checks this
        after every completed trace, so a scheduling path that leaks or
        double-frees pages fails loudly in ANY test that serves to
        completion.  A pool poisoned by an abnormal serve exit (escaped
        ``EngineCrash``/fault mid-round) fails until the next serve's
        ``_reset`` — its mid-trace state proves nothing either way."""
        if self._pool_poisoned:
            raise AssertionError(
                "page pool poisoned: a serve trace aborted mid-round, so "
                "allocator state is mid-flight, not quiescent; start a new "
                "serve (or _reset) before asserting invariants")
        self._pool.assert_quiescent()

    # ------------------------------------------------------------ lifecycle --
    def _reset(self, requests, n_stops: int):
        b, w = self.slots, self.width
        self._cache = init_paged_cache(self.cfg, b, self._store_seq,
                                       self.num_pages, self.page_size)
        # The draft model's OWN paged pool: same geometry and the same
        # block tables as the target's, so one host-side page allocator
        # covers both and admit/retire/preemption keep them in lockstep.
        self._dcache = (init_paged_cache(self.draft_cfg, b, self._store_seq,
                                         self.num_pages, self.page_size)
                        if self._draft_mode else ())
        # Refcounted page pool + (fresh) prefix trie: trie-registered pages
        # are only valid against THIS pool's device storage, so both reset
        # together — prefix reuse is within one serve trace, which is where
        # repeated system prompts actually collide.  Page 0 = trash.
        self._trie = PrefixTrie() if self._prefix_on else None
        self._pool = PagePool(
            self.num_pages, rng=self._rng,
            on_evict=self._trie.drop_page if self._trie is not None else None)
        self._pool_poisoned = False
        self._plen = np.zeros(b, np.int32)  # prompt length per slot
        self._bt = np.zeros((b, w), np.int32)
        self._pos = np.zeros(b, np.int32)
        self._n_out = np.zeros(b, np.int32)
        self._done = np.ones(b, bool)  # inactive slots are "done"
        self._max_new = np.zeros(b, np.int32)
        self._stops = np.full((b, n_stops), -1, np.int32)
        self._tok = np.zeros((b, 1), np.int32)
        # per-slot request id and verify-window counter: the (rid, counter)
        # pair keys every sampled draw, so a request's random stream is
        # slot- and schedule-independent (sampling.draw_keys)
        self._rids = np.zeros(b, np.int32)
        self._wctr = np.zeros(b, np.int32)
        self._slot_req = [-1] * b
        self._slot_pages: list[list[int]] = [[] for _ in range(b)]
        self._admit_seq = [-1] * b
        self._seq = 0
        self._outputs = [[] for _ in requests]
        self._queue = deque(range(len(requests)))
        self._extras_slots = None
        # per-slot token history (prompt + emissions) for the n-gram
        # proposer; rewritten whole at admit, so stale rows never leak
        self._hist = np.zeros((b, self.max_seq), np.int32)
        # per-slot acceptance EMA for the adaptive controller: updated by
        # the spec chunk, read by ``adaptive_k_host`` each round, carried
        # through snapshots so crash replay resumes the learned rate
        self._acc_ema = np.zeros(b, np.float32)
        # controller probation: a freshly admitted slot gets one SHORT
        # round before the controller commits to a full-length one, so
        # the first wide window is picked from a measured EMA rather
        # than ``ctrl_init``
        self._ctrl_fresh = np.zeros(b, bool)

    def _prefix_probe(self, req, resume):
        """(chunk keys, extras fingerprint, matched trie pages) for a fresh
        request under an active prefix cache; ``([], None, [])`` otherwise.
        Pure probe — no refcount or LRU side effects, so the admission
        gate and ``_admit`` can both call it.  Resume admits never match:
        their rebuilt sequence embeds emitted tokens and must replay
        through the exact full-prefill path the snapshot semantics pin."""
        if not self._prefix_on or resume is not None:
            return [], None, []
        keys = chunk_keys(np.asarray(req.prompt, np.int32), self.page_size)
        fp = extras_fingerprint(req.extras)
        return keys, fp, self._trie.match(keys, fp)

    def _admit_page_need(self, req, resume) -> tuple[int, list[int]]:
        """(fresh pages the admit itself would allocate, trie pages it
        would alias) — the admission gate's capacity probe.  Admission is
        deliberately optimistic (prompt footprint only, not the first
        chunk's growth): if the same round's ``_top_up`` then finds the
        pool dry, the freshly admitted slot — necessarily the youngest —
        YIELDS by requeueing itself rather than preempting an elder, so
        optimism can waste a prefill but can never livelock (see
        ``_top_up``)."""
        L = len(req.prompt) + (len(resume.emitted) - 1 if resume else 0)
        total = self._spad(L) // self.page_size
        _, _, matched = self._prefix_probe(req, resume)
        if matched and len(matched) == total and L == total * self.page_size:
            # Full-prefix hit: only the CoW fork of the last page is fresh.
            return 1, matched
        return total - len(matched), matched

    def _admit(self, requests, slot: int, ridx: int, greedy, temperature,
               top_k, resume: Optional[InflightState] = None) -> dict:
        """Admit request ``ridx`` into ``slot``; returns admit telemetry
        (``cached_tokens``/``prefilled_tokens``/``cow``).

        A prefix-trie hit aliases the matched pages into the slot's block
        table (refcount + 1 each) and computes only the unmatched tail via
        ONE ``models.verify_step`` window (``_tail_verify``) — sampling the
        first token from the logits at the true prompt end with the same
        (rid, 0) draw key as the full-prefill path, so a hit is
        token-identical to a miss.  A FULL-prefix hit still has to run the
        last prompt position for its logits, and that write lands inside
        the final matched page — the page is forked copy-on-write first
        (``_copy_page``), so the trie's copy and every aliasing sibling
        keep their bytes.  An uncached admit full-prefills as before and
        then registers its prompt's full pages in the trie.

        With ``resume`` (crash replay, resume_mode="prefill") the request
        is re-admitted mid-stream: ONE prefill pass over
        ``prompt + emitted[:-1]`` rebuilds its KV pages, the last emission
        becomes the slot's current token, and the token draw counter
        restarts at ``len(emitted)`` — the fold_in (rid, counter) keys
        then continue the exact random stream the crashed run was
        consuming, so replay is token-identical."""
        req = requests[ridx]
        ps = self.page_size
        length = len(req.prompt)
        rid = ridx if req.rid is None else int(req.rid)
        emitted = [int(t) for t in resume.emitted] if resume is not None else []
        m = len(emitted)
        seq = np.asarray(req.prompt, np.int32)
        if m:
            seq = np.concatenate(
                [seq, np.asarray(emitted[:-1], np.int32)])
        L = len(seq)  # length + m - 1 when resuming
        spad = self._spad(L)
        total = spad // ps
        ex1 = self._set_slot_extras(slot, req.extras)
        keys, fp, matched = self._prefix_probe(req, resume)
        cow = False
        if matched and len(matched) == total and L == total * ps:
            # Full-prefix hit: every prompt position is cached, but the
            # logits at L-1 must still be computed, and verify_step writes
            # that position's K/V — into the final matched page, which the
            # trie (and possibly siblings) still read.  Fork it.
            for p in matched:
                self._pool.acquire(p)
            fork = self._pool.alloc(1)[0]
            self._cache = _copy_page(
                self._cache, jnp.int32(matched[-1]), jnp.int32(fork),
                keys=_pool_leaf_paths(self.cfg))
            self._pool.release(matched[-1])
            pages = matched[:-1] + [fork]
            tail_start = L - 1
            cow = True
            self.cow_forks += 1
        elif matched:
            # Partial hit: alias the matched pages, allocate only the tail.
            for p in matched:
                self._pool.acquire(p)
            pages = matched + self._pool.alloc(total - len(matched))
            tail_start = len(matched) * ps
        else:
            pages = self._pool.alloc(total)
            tail_start = 0
        self._bt[slot, :] = 0
        self._bt[slot, : len(pages)] = pages
        if matched:
            # Cached admit: one verify window over the padded tail.  Only
            # this slot's block-table row is exposed — every other row's
            # window writes go to the trash page.
            tokens = np.zeros((self.slots, spad - tail_start), np.int32)
            tokens[slot, : L - tail_start] = seq[tail_start:]
            pos = np.zeros(self.slots, np.int32)
            pos[slot] = tail_start
            bt_masked = np.zeros_like(self._bt)
            bt_masked[slot] = self._bt[slot]
            self._cache["block_tables"] = jnp.asarray(bt_masked)
            tail = (_tail_verify if self.mesh is None else functools.partial(
                _tail_verify_sharded, mesh=self.mesh))
            self._cache, tok0 = tail(
                self.params, self.cfg, self._cache, jnp.asarray(tokens),
                jnp.asarray(pos), jnp.int32(slot), jnp.int32(rid),
                jnp.int32(L - 1 - tail_start), self._key,
                jnp.float32(temperature), self._extras_slots,
                greedy=bool(greedy), top_k=int(top_k),
                page_size=self.page_size)
            self.prefix_hits += 1
            self.prefix_hit_tokens += tail_start
            self.prefill_tokens += L - tail_start
        else:
            prompt = np.zeros((1, spad), np.int32)
            prompt[0, :L] = seq
            admit = (_admit_prefill if self.mesh is None
                     else functools.partial(_admit_prefill_sharded,
                                            mesh=self.mesh))
            self._cache, tok0, bad = admit(
                self.params, self.cfg, self._cache, jnp.asarray(prompt),
                jnp.int32(L), jnp.int32(slot), jnp.asarray(pages, jnp.int32),
                jnp.int32(rid), self._key, jnp.float32(temperature), ex1,
                greedy=bool(greedy), top_k=int(top_k))
            self.nonfinite_logits += int(bad)
            if self._draft_mode:
                # Prefill the draft pool's copy of the prompt into the SAME
                # page ids (its own storage); the draft admit's sample is
                # discarded — tok0 always comes from the target.
                self._dcache, _, _ = _admit_prefill(
                    self.draft_params, self.draft_cfg, self._dcache,
                    jnp.asarray(prompt), jnp.int32(L), jnp.int32(slot),
                    jnp.asarray(pages, jnp.int32), jnp.int32(rid), self._key,
                    jnp.float32(temperature), ex1, greedy=True, top_k=0)
            self.prefill_tokens += L
            if self._prefix_on and resume is None:
                # Register the prompt's FULL pages: their positions are
                # final (decode writes start at L) and their content came
                # from the exact full-prefill computation a later miss
                # would run, so hits can be bit-identical.  Verify-written
                # tail pages of cached admits are never registered.
                self._trie.insert(keys, fp, pages,
                                  on_new=self._pool.mark_cached)
        if not m:
            # Fresh admit: the prefill's sample IS emission 0 (draw key 0).
            emitted = [int(tok0)]
        # Resume admit: the prefill re-sampled draw 0 — discarded; draws
        # are keyed by (rid, counter), not sequentially consumed, so the
        # stream resumes at counter m untouched.
        st = tuple(req.stop_tokens)
        self._outputs[ridx] = list(emitted)
        self._hist[slot, :] = 0
        self._hist[slot, :length] = np.asarray(req.prompt, np.int32)
        self._hist[slot, length : length + len(emitted)] = emitted
        self._plen[slot] = length
        self._pos[slot] = length + len(emitted) - 1
        self._n_out[slot] = len(emitted)
        self._max_new[slot] = req.max_new
        self._stops[slot, :] = -1
        self._stops[slot, : len(st)] = st
        self._tok[slot, 0] = emitted[-1]
        self._rids[slot] = rid
        self._wctr[slot] = int(resume.wctr) if resume is not None else 0
        self._acc_ema[slot] = (float(resume.acc_ema) if resume is not None
                               else (self.spec.ctrl_init
                                     if self.spec is not None else 0.0))
        self._ctrl_fresh[slot] = True
        self._done[slot] = (len(emitted) >= req.max_new
                            or emitted[-1] in st)
        self._slot_req[slot] = ridx
        self._slot_pages[slot] = list(pages)
        self._admit_seq[slot] = self._seq
        self._seq += 1
        return {"cached_tokens": tail_start,
                "prefilled_tokens": L - tail_start if matched else L,
                "cow": cow}

    def _retire(self, slot: int) -> None:
        self._free_pages(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._slot_req[slot] = -1
        self._admit_seq[slot] = -1
        self._bt[slot, :] = 0
        self._pos[slot] = 0
        self._n_out[slot] = 0
        self._max_new[slot] = 0
        self._stops[slot, :] = -1
        self._rids[slot] = 0
        self._wctr[slot] = 0
        self._acc_ema[slot] = 0.0
        self._ctrl_fresh[slot] = False
        self._done[slot] = True

    def _preempt_slot(self, victim: int) -> None:
        """Recompute preemption: requeue ``victim``'s request at the queue
        head and free its pages.  Progress is discarded; replay is exact
        (draws are (rid, counter)-keyed)."""
        ridx = self._slot_req[victim]
        self._outputs[ridx].clear()
        self._queue.appendleft(ridx)
        self._retire(victim)
        self.preemptions += 1
        if self._records is not None:
            self._records[ridx].events.append(
                {"name": "preempt", "ts": self._now(), "slot": victim})

    def _top_up(self, requests, slot: int,
                eff_chunk: Optional[int] = None,
                eff_k: Optional[int] = None) -> None:
        """Extend the slot's block table to cover the next chunk's writes.
        If the pool runs dry, younger live requests are recompute-preempted
        — unless THIS slot is the youngest, in which case it yields
        (requeues itself) so elders keep their progress; see the loop
        below for why preempting upward would livelock.

        ``eff_chunk``/``eff_k`` are the ROUND's effective scheduling
        parameters (the degradation ladder may shrink them below the
        engine's configured ``chunk``/``spec.k``; ``eff_k=None`` means no
        speculative window this round, so no verify-window overdraw)."""
        req = requests[self._slot_req[slot]]
        ps = self.page_size
        length = len(req.prompt)
        spad = self._spad(length)
        # Live CONSUMED positions in the next chunk reach pos + advance - 1
        # (advance = chunk steps x the window's worst-case accepted length),
        # bounded by the last live write position length + max_new - 2;
        # prefill already covered spad - 1.  Speculative writes BEYOND the
        # consumed frontier need no pages for the TARGET: an unprovisioned
        # block-table entry is 0, the trash page, and the verify window
        # attends to its own in-flight K/V, so a token only ever gets
        # consumed after being rewritten into a provisioned page.  The
        # DRAFT chain, however, runs k+1 sequential single-token steps that
        # READ BACK the window positions they just wrote, so draft mode
        # provisions up to k positions past the consumed cap (the pools
        # carry k extra positions past max_seq for exactly this — see
        # ``_store_seq``) to keep those reads out of the shared trash page:
        # a trash read would only degrade proposal quality, never
        # exactness, but it would break cross-engine key-determinism.
        chunk = self.chunk if eff_chunk is None else eff_chunk
        k = (self.spec.k if self.spec is not None else None) \
            if eff_chunk is None else eff_k  # default call = engine config
        fan = self.spec.tree_fan if self.spec is not None else 0
        adv = chunk * (k + 1 if k is not None else 1)
        cap = length + req.max_new - 2
        if self._draft_mode and k is not None:
            cap = min(cap + k, self._store_seq - 1)
        if fan and k is not None:
            # Tree relocation GATHERS from the tree columns (up to
            # pos + fan*k past the frontier) before scattering into the
            # linear layout — those sources must be provisioned pages, or
            # the gather reads the shared trash page and corrupts
            # committed positions.  Each iteration advances at most k+1.
            adv = chunk * (k + 1) + fan * k
            cap = min(cap + fan * k, self._store_seq - 1)
        last = min(int(self._pos[slot]) + adv - 1, cap)
        need = max(last, spad - 1) // ps + 1
        have = len(self._slot_pages[slot])
        if need <= have:
            return
        while self._pool.available() < need - have:
            live = [s for s in range(self.slots) if self._slot_req[s] >= 0]
            youngest = max(live, key=lambda s: self._admit_seq[s])
            if youngest != slot:
                self._preempt_slot(youngest)
                continue
            if len(live) == 1:
                raise RuntimeError(
                    f"page pool exhausted ({self.num_pages} pages of "
                    f"{ps} tokens) with a single live request; increase "
                    "num_pages")
            # This slot is the YOUNGEST live request and the pool is dry:
            # yield by requeueing ITSELF instead of stealing pages from an
            # elder that already has progress.  Preempting upward here is
            # the livelock: on a pool just big enough to re-admit the
            # victim, two symmetric requests alternate evicting each other
            # pre-decode forever (each re-admit's same-round top-up fires
            # before either emits a token, and recompute preemption
            # discards everything).  Yielding makes progress monotone for
            # the oldest request — it always completes, frees its pages,
            # and unblocks the queue.
            self._preempt_slot(slot)
            return
        pages = self._alloc_pages(need - have)
        self._bt[slot, have:need] = pages
        self._slot_pages[slot].extend(pages)

    # --------------------------------------------------------------- serve --
    def serve(self, requests: Sequence[Request], *, greedy: bool = True,
              temperature: float = 1.0, top_k: int = 0, key=None,
              policy=None, chaos=None) -> list[np.ndarray]:
        """Run every request through the scheduler; returns one int32 array
        of emitted tokens per request (<= max_new; ends at the stop token
        if one fired).  Deterministic for a fixed key — and because draws
        are keyed per (request index in the trace, counter), a request's
        sampled tokens are independent of slot assignment, chunk size, and
        page allocation, and match the dense fixed-batch engine run in
        which it occupies the SAME batch row index (the fixed engine keys
        row i's draws by rid=i).  A solo batch-1 dense run matches request
        0 only; greedy decode matches solo runs regardless.

        Thin wrapper over ``serve_detailed`` (which adds per-request
        deadlines/SLOs, load shedding, fault retry, degradation, and crash
        snapshots under a ``resilience.ResiliencePolicy``); without a
        policy the scheduler behaves exactly as before — invalid requests
        raise, faults propagate.  The full ``ServeReport`` of the last
        call is kept on ``self.last_report``."""
        report = self.serve_detailed(
            requests, greedy=greedy, temperature=temperature, top_k=top_k,
            key=key, policy=policy, chaos=chaos)
        return [r.tokens for r in report.records]

    def decode_chunk_text(self) -> str:
        """Compiled text of the plain greedy decode-chunk program at this
        engine's shapes, as ``serve`` runs it — for checking what the
        compiler put in it (e.g. the ``tpu_custom_call`` of the
        ``pim_matvec`` kernel).  Call after a ``serve``: the engine's slot
        state gives the shapes."""
        args = (self.params, self.cfg, self._cache, jnp.asarray(self._tok),
                jnp.asarray(self._pos), jnp.asarray(self._n_out),
                jnp.asarray(self._done), jnp.asarray(self._rids),
                jnp.asarray(self._max_new), jnp.asarray(self._stops),
                self._key, jnp.float32(1.0), self._extras_slots)
        kw = dict(chunk=self.chunk, page_size=self.page_size,
                  greedy=True, top_k=0, pad_id=self.pad_id)
        if self.mesh is None:
            lowered = _decode_chunk.lower(*args, **kw)
        else:
            lowered = _decode_chunk_sharded.lower(*args, mesh=self.mesh, **kw)
        return lowered.compile().as_text()

    def _span(self, name: str, **args) -> _Span:
        """Open a host span of this serve call (``ServeReport.spans``)."""
        return _Span(self._spans, self._now, name, **args)

    def _shed(self, records, report, ridx: int, reason: str) -> None:
        rec = records[ridx]
        rec.status, rec.reason = "shed", reason
        rec.tokens = np.asarray(self._outputs[ridx], np.int32)
        rec.events.append({"name": "shed", "ts": self._now(),
                           "reason": reason})
        report.sheds += 1

    def _finish(self, requests, records, slot: int, t: float) -> None:
        """Retire a finished slot, stamping completion time and deadline
        attainment on its record.  ``t`` is the slot's OWN completion
        estimate — the round boundary interpolated to the chunk iteration
        the slot actually finished in (see ``ServeReport.latencies`` for
        the residual quantization)."""
        ridx = self._slot_req[slot]
        rec = records[ridx]
        rec.tokens = np.asarray(self._outputs[ridx], np.int32)
        rec.status = "done"
        rec.t_done = t
        rec.events.append({"name": "finish", "ts": t,
                           "tokens": len(rec.tokens)})
        dl = requests[ridx].deadline
        rec.met_deadline = None if dl is None else bool(t <= dl)
        self._retire(slot)

    def _take_snapshot(self, records, policy, rnd: int) -> ServeSnapshot:
        """Host-side recovery point: finished outputs + in-flight replay
        state (emitted tokens + verify-window counter, admit order
        preserved) + the queue.  No device state — resume rebuilds KV
        pages by re-prefilling (see ``_admit``)."""
        live = sorted((s for s in range(self.slots)
                       if self._slot_req[s] >= 0),
                      key=lambda s: self._admit_seq[s])
        inflight = {}
        for s in live:
            ridx = self._slot_req[s]
            inflight[ridx] = InflightState(
                emitted=[int(t) for t in self._outputs[ridx]],
                wctr=int(self._wctr[s]),
                t_admit=records[ridx].t_admit,
                t_first=records[ridx].t_first,
                acc_ema=float(self._acc_ema[s]))
        snap = ServeSnapshot(
            finished={i: [int(t) for t in self._outputs[i]]
                      for i, r in enumerate(records) if r.status == "done"},
            inflight=inflight,
            queued=list(self._queue),
            closed={i: (r.status, r.reason) for i, r in enumerate(records)
                    if r.status in ("shed", "rejected")},
            round=rnd)
        self.last_snapshot = snap
        if policy is not None and policy.snapshot_sink is not None:
            policy.snapshot_sink(snap)
        return snap

    def serve_detailed(self, requests: Sequence[Request], *,
                       greedy: bool = True, temperature: float = 1.0,
                       top_k: int = 0, key=None,
                       policy: Optional[ResiliencePolicy] = None,
                       chaos=None, resume: Optional[ServeSnapshot] = None,
                       heartbeat=None) -> ServeReport:
        """``serve`` with the resilience layer: returns a ``ServeReport``
        with per-request outcomes (done/shed/rejected + timing) and the
        round-level counters.  See ``serving.resilience`` for the full
        failure semantics (what is retried, shed, rejected, degraded, and
        replayed).

        ``policy`` enables request-level robustness: admission validation
        (invalid/corrupt payloads become status "rejected" instead of
        raising), deadline and queue-bound load shedding, per-chunk
        retry-with-backoff for transient ``ChunkFault``s, the degradation
        ladder, and periodic ``ServeSnapshot``s.  ``chaos`` (a
        ``chaos.FaultInjector``) injects seeded failures at the scheduling
        boundaries; passing chaos without a policy gets the default
        ``ResiliencePolicy()``.  ``resume`` replays a snapshot: finished/
        closed requests keep their outcome, in-flight requests re-admit
        mid-stream (resume_mode="prefill"; exact for every family whose
        prefill and decode paths agree bit-wise — MLA's absorbed decode
        differs at ~1e-3, use "recompute" there) or requeue from scratch
        ("recompute", universally exact, same semantics as recompute
        preemption).  ``heartbeat`` is called once per scheduling round
        (the supervisor's liveness signal).  Timing (``t_admit``/
        ``t_done``/deadlines) is engine-clock seconds from THIS call's
        start, plus accumulated skew: injected straggler latency, retry
        backoff, and ``policy.round_time`` per round — fully deterministic
        under a ``chaos.VirtualClock``.

        On ``EngineCrash`` (injected, retry exhaustion, or a wrapped
        compiled-step failure) the latest snapshot stays on
        ``self.last_snapshot`` for the supervisor to replay."""
        if chaos is not None and policy is None:
            policy = ResiliencePolicy()
        hardened = policy is not None
        ex_struct = jax.tree.structure(requests[0].extras) if requests else None
        records = [RequestRecord() for _ in requests]
        rejected_upfront: set[int] = set()
        for i, r in enumerate(requests):
            bad = None
            if len(r.prompt) < 1 or r.max_new < 1:
                bad = "requests need len(prompt) >= 1, max_new >= 1"
            elif len(r.prompt) + r.max_new > self.max_seq:
                bad = (f"prompt ({len(r.prompt)}) + max_new ({r.max_new}) "
                       f"exceeds max_seq ({self.max_seq})")
            if bad is not None:
                if not hardened:
                    raise ValueError(bad)
                records[i].status, records[i].reason = "rejected", bad
                rejected_upfront.add(i)
                continue
            if jax.tree.structure(r.extras) != ex_struct:
                raise ValueError(
                    "all requests in a trace must share the same extras "
                    "structure (the decode chunk is one compiled program)")
        self._key = key if key is not None else jax.random.PRNGKey(0)
        n_stops = max((len(r.stop_tokens) for r in requests), default=0)
        self._reset(requests, n_stops)
        self.peak_pages_in_use = 0
        self.spec_emitted = 0
        self.spec_live_steps = 0
        self.decode_chunk_iters = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.prefill_tokens = 0
        self.cow_forks = 0
        report = ServeReport(records=records)
        report.rejects += len(rejected_upfront)
        clock = self._clock
        t0 = clock()
        skew = 0.0  # injected latency + retry backoff + per-round time

        def now() -> float:
            return (clock() - t0) + skew

        self._records = records
        self._now = now
        self._spans = report.spans

        ladder = DegradationLadder(
            policy.ladder if hardened else LadderConfig(enabled=False),
            has_spec=self.spec is not None)
        # ---- resume: restore finished/closed outcomes, rebuild the queue
        resume_inflight: dict[int, InflightState] = {}
        if resume is not None:
            for ridx, toks in resume.finished.items():
                records[ridx].status = "done"
                records[ridx].tokens = np.asarray(toks, np.int32)
                self._outputs[ridx] = [int(t) for t in toks]
            for ridx, (st, reason) in resume.closed.items():
                if ridx in rejected_upfront:
                    continue  # already re-rejected (and counted) upfront
                records[ridx].status, records[ridx].reason = st, reason
                if st == "shed":
                    report.sheds += 1
                else:
                    report.rejects += 1
            if hardened and policy.resume_mode == "prefill":
                resume_inflight = dict(resume.inflight)
                for ridx, st in resume.inflight.items():
                    records[ridx].t_admit = st.t_admit
                    records[ridx].t_first = st.t_first
            # "recompute" (or no policy): in-flight requests requeue from
            # scratch — same semantics as recompute preemption.
            self._queue = deque(
                list(resume.inflight)
                + [r for r in resume.queued if r not in rejected_upfront])
        else:
            self._queue = deque(i for i in range(len(requests))
                                if i not in rejected_upfront)
        self.last_snapshot = None
        snap_every = policy.snapshot_every if hardened else 0
        if snap_every:
            self._take_snapshot(records, policy, -1)

        rnd = 0
        # Exception safety: any abnormal exit from the round loop (escaped
        # EngineCrash, chunk fault, compiled-step failure) leaves allocator
        # state mid-trace — mark the pool poisoned until the next
        # ``_reset`` so it can't masquerade as quiescent and leaks can't
        # be silently rebuilt away.  Cleared on normal completion below.
        self._pool_poisoned = True
        while self._queue or any(r >= 0 for r in self._slot_req):
            self.last_round = rnd
            if heartbeat is not None:
                with self._span("engine.heartbeat", round=rnd):
                    heartbeat()
            if chaos is not None:
                chaos.crash(rnd)  # raises EngineCrash; supervisor replays
            schedule = self._span("engine.schedule", round=rnd)
            retries_before = report.retries
            preempt_before = self.preemptions
            sheds_round = 0
            # ---- queue management: deadline sheds, bounded queue, ladder
            if hardened:
                t = now()
                if policy.shed_expired:
                    for ridx in list(self._queue):
                        dl = requests[ridx].deadline
                        if dl is not None and t > dl:
                            self._queue.remove(ridx)
                            self._shed(records, report, ridx, "deadline")
                            sheds_round += 1
                if policy.max_queue is not None:
                    while len(self._queue) > policy.max_queue:
                        q = list(self._queue)
                        # lowest SLO class first; ties shed the youngest
                        i = min(range(len(q)),
                                key=lambda j: (requests[q[j]].slo, -j))
                        self._queue.remove(q[i])
                        self._shed(records, report, q[i], "queue")
                        sheds_round += 1
                if ladder.shedding():
                    for ridx in list(self._queue):
                        if requests[ridx].slo < ladder.cfg.protect_slo:
                            self._queue.remove(ridx)
                            self._shed(records, report, ridx, "ladder")
                            sheds_round += 1
            # ---- admit queued requests into free slots while pages last
            admitted_any = False
            blocked = False
            for slot in range(self.slots):
                if blocked or self._slot_req[slot] >= 0:
                    continue
                while self._queue:
                    ridx = self._queue[0]
                    if ridx in self._debug_drop_rids:
                        # Test hook: simulate a scheduler bug that loses a
                        # request on the floor, so the strict pending sweep
                        # below is itself testable.
                        self._queue.popleft()
                        continue
                    req = requests[ridx]
                    if hardened and req.arrival > now():
                        blocked = True  # FIFO: an unarrived head waits
                        break
                    prompt = np.asarray(req.prompt)
                    if chaos is not None:
                        prompt = chaos.corrupt_request(prompt, ridx, rnd)
                    if hardened and policy.validate:
                        arr = np.asarray(prompt)
                        if arr.size and (int(arr.min()) < 0
                                         or int(arr.max()) >= self.cfg.vocab):
                            self._queue.popleft()
                            records[ridx].status = "rejected"
                            records[ridx].reason = "corrupt"
                            report.rejects += 1
                            continue  # slot still free: try the next head
                    rs = resume_inflight.pop(ridx, None)
                    need, reserve = self._admit_page_need(req, rs)
                    if self._pool.available(reserve) < need:
                        if rs is not None:
                            resume_inflight[ridx] = rs  # retry next round
                        blocked = True
                        break
                    self._queue.popleft()
                    with self._span("engine.admit", round=rnd, slot=slot,
                                    prompt_tokens=len(req.prompt)) as sp:
                        info = self._admit(requests, slot, ridx, greedy,
                                           temperature, top_k, resume=rs)
                        sp.late["prefilled_tokens"] = info["prefilled_tokens"]
                    rec = records[ridx]
                    rec.slot = slot
                    if rec.t_admit is None:
                        rec.t_admit = now()
                        rec.t_first = rec.t_admit
                    rec.events.append({"name": "admit", "ts": now(),
                                       "slot": slot, "round": rnd, **info})
                    admitted_any = True
                    break
            # Retire anything that finished at admit (max_new==1 / instant
            # stop) so its slot and pages free up immediately.
            t_adm = now()
            for slot in range(self.slots):
                if self._slot_req[slot] >= 0 and self._done[slot]:
                    self._finish(requests, records, slot, t_adm)
            live = [s for s in range(self.slots) if self._slot_req[s] >= 0]
            if not live:
                schedule.close()
                idle = self._span("engine.idle", round=rnd)
                if self._queue and not admitted_any:
                    head = self._queue[0]
                    if hardened and requests[head].arrival > now():
                        # Idle until the head arrives; advance deterministic
                        # time so a virtual clock cannot spin forever.
                        skew += policy.round_time or policy.backoff_s
                    elif hardened:
                        self._queue.popleft()
                        self._shed(records, report, head, "oom")
                    else:
                        # Nothing running and the head could not admit.
                        raise RuntimeError(
                            "page pool too small to admit request with "
                            f"prompt {len(requests[head].prompt)} tokens; "
                            "increase num_pages")
                idle.close()
                rnd += 1
                continue
            # ---- effective scheduling parameters for this round (ladder)
            eff_chunk, eff_k = ladder.params(
                self.chunk, self.spec.k if self.spec is not None else None)
            if (self.spec is not None and self.spec.adaptive
                    and eff_k is not None):
                # Adaptive controller: the verify-window width is SHARED
                # across the batch (one compiled program per round), so
                # the round's k comes from the batch-aggregate expected
                # gain over the per-slot acceptance EMAs — composed with
                # the ladder as min(rung, controller).  A k == 0 pick
                # dispatches the genuine plain decode chunk below (the
                # fixed engine instead runs width-0 windows with the
                # in-loop ``_ctrl_probe``).
                alive = np.asarray(
                    [self._slot_req[s] >= 0 and not self._done[s]
                     for s in range(self.slots)])
                eff_k = min(eff_k,
                            spec_mod.adaptive_k_host(self._acc_ema, alive,
                                                     self.spec))
                # Shrink the chunk to the longest live remaining budget:
                # iterations past every slot's max_new stream weights for
                # nothing, and chunk boundaries never change a request's
                # token stream (draws are (rid, counter)-keyed).
                rem = int(max(
                    (self._max_new[s] - self._n_out[s]
                     for s in range(self.slots) if alive[s]), default=1))
                # Admission happens only at round boundaries, so when
                # requests are WAITING a slot that finishes mid-round
                # idles until the round ends.  End the round where the
                # first live slot can free (its remaining budget), and
                # the top-up refills it immediately — the fixed-chunk
                # plain baseline eats that idle tail.
                if self._queue:
                    rem = min(rem, int(min(
                        (self._max_new[s] - self._n_out[s]
                         for s in range(self.slots) if alive[s]),
                        default=rem)))
                if eff_k > 0:
                    # Wide window: cap the round at ceil(rem/(k+1))
                    # iterations — enough to cover the longest remaining
                    # budget — so the controller re-picks k from fresh
                    # EMAs instead of riding one stale pick for a whole
                    # ``chunk``.  A round containing a freshly admitted
                    # slot is cut to a 2-iteration probation round so its
                    # first full-width window is priced from a MEASURED
                    # acceptance rate, not ``ctrl_init``.
                    eff_chunk = min(eff_chunk, -(-rem // (eff_k + 1)))
                    if alive.any() and self._ctrl_fresh[alive].any():
                        eff_chunk = min(eff_chunk, 2)
                else:
                    # Speculation is losing (or unmeasured): genuinely
                    # fall back to the PLAIN decode chunk.  The
                    # ladder-degrade path keeps the n-gram history warm,
                    # and the host-side probe in the plain emit loop
                    # (``propose_first_host``) keeps the EMA learning at
                    # zero device cost, so a regime change is picked up
                    # at the next round boundary — no probe rounds, no
                    # short rounds, no spec-chunk overhead on text where
                    # speculation cannot pay.
                    eff_k = None
                    eff_chunk = min(eff_chunk, rem)
                if alive.any():
                    self._ctrl_fresh[alive] = False
                # Quantize the cap to a power of two (or the full chunk)
                # so the jitted chunk compiles O(log chunk) shapes, not
                # one per distinct remaining-budget value.
                eff_chunk = max(1, eff_chunk)
                if eff_chunk < self.chunk:
                    eff_chunk = 1 << (eff_chunk.bit_length() - 1)
            spec_on = self.spec is not None and eff_k is not None
            # ---- page top-up, under injected pool pressure
            withheld: list[int] = []
            if chaos is not None:
                n_w = chaos.squeeze_pages(len(self._pool.free), rnd)
                if n_w:
                    # Withhold from the free list only: retained
                    # prefix-cache pages stay evictable, so a squeeze
                    # squeezes the CACHE first — exactly the
                    # opportunistic-capacity contract.
                    withheld = self._pool.free[-n_w:]
                    del self._pool.free[-n_w:]
                    report.squeezed_pages += n_w

            def _top_ups():
                for s in live:
                    # An earlier top-up in this round may have preempted
                    # this slot — don't grow a retired slot.
                    if self._slot_req[s] >= 0:
                        self._top_up(requests, s, eff_chunk, eff_k)

            try:
                _top_ups()
            except RuntimeError:
                if withheld:
                    # The squeeze alone exhausted the pool: give the pages
                    # back and retry before escalating.
                    self._pool.free.extend(withheld)
                    withheld = []
                    try:
                        _top_ups()
                    except RuntimeError:
                        if not hardened:
                            raise
                        withheld = None  # sentinel: shed below
                elif hardened:
                    withheld = None
                else:
                    raise
            if withheld is None:
                # Pool genuinely too small for the single remaining live
                # request: shed it with its partial output.
                s0 = next(s for s in range(self.slots)
                          if self._slot_req[s] >= 0)
                ridx = self._slot_req[s0]
                self._shed(records, report, ridx, "oom")
                self._retire(s0)
                schedule.close()
                rnd += 1
                continue
            if withheld:
                self._pool.free.extend(withheld)
            self.peak_pages_in_use = max(self.peak_pages_in_use,
                                         self.pages_in_use())
            # ---- transient chunk faults: retry with (virtual) backoff
            if chaos is not None:
                attempt = 0
                while True:
                    try:
                        chaos.chunk_fault(rnd)
                        break
                    except ChunkFault as e:
                        report.retries += 1
                        if attempt >= policy.max_retries:
                            raise EngineCrash(
                                f"chunk retries exhausted: {e}") from e
                        skew += policy.backoff_s * (2.0 ** attempt)
                        attempt += 1
                lag = chaos.chunk_latency(rnd)
                skew += lag
                report.straggle_s += lag

            schedule.close()
            n0 = self._n_out.copy()
            t_round_start = now()
            ema = None
            try:
                dispatch = self._span("engine.dispatch", round=rnd)
                self._cache["block_tables"] = jnp.asarray(self._bt)
                self.decode_chunk_iters += eff_chunk
                if spec_on and self.spec.tree_fan:
                    step = (spec_mod._spec_tree_chunk if self.mesh is None
                            else functools.partial(
                                spec_mod._spec_tree_chunk_sharded,
                                mesh=self.mesh))
                    (self._cache, tok, pos, n_out, done, hist, wctr,
                     emits, ms) = step(
                        self.params, self.cfg, self._cache,
                        jnp.asarray(self._tok), jnp.asarray(self._pos),
                        jnp.asarray(self._n_out), jnp.asarray(self._done),
                        jnp.asarray(self._hist), jnp.asarray(self._wctr),
                        jnp.asarray(self._rids), jnp.asarray(self._max_new),
                        jnp.asarray(self._stops), self._key,
                        jnp.float32(temperature), self._extras_slots,
                        chunk=eff_chunk, page_size=self.page_size,
                        fan=self.spec.tree_fan, depth=eff_k,
                        ngram_n=self.spec.ngram_n, pad_id=self.pad_id,
                        greedy=bool(greedy), top_k=int(top_k))
                elif spec_on:
                    if self._draft_mode:
                        self._dcache["block_tables"] = jnp.asarray(self._bt)
                    if self.mesh is None:
                        (self._cache, self._dcache, tok, pos, n_out, done,
                         hist, wctr, ema, emits, ms) = spec_mod._spec_chunk(
                            self.params, self.cfg, self._cache,
                            self.draft_params, self._dcache,
                            jnp.asarray(self._tok), jnp.asarray(self._pos),
                            jnp.asarray(self._n_out), jnp.asarray(self._done),
                            jnp.asarray(self._hist), jnp.asarray(self._wctr),
                            jnp.asarray(self._acc_ema),
                            jnp.asarray(self._rids), jnp.asarray(self._max_new),
                            jnp.asarray(self._stops), self._key,
                            jnp.float32(temperature), self._extras_slots,
                            draft_cfg=self.draft_cfg, chunk=eff_chunk,
                            page_size=self.page_size, k=eff_k,
                            mode=self.spec.mode, ngram_n=self.spec.ngram_n,
                            pad_id=self.pad_id, greedy=bool(greedy),
                            top_k=int(top_k), adaptive=self.spec.adaptive,
                            ctrl_alpha=self.spec.ctrl_alpha,
                            accept=self.spec.accept,
                            typical_eps=self.spec.typical_eps,
                            typical_delta=self.spec.typical_delta)
                    else:
                        (self._cache, tok, pos, n_out, done, hist, wctr,
                         ema, emits, ms) = spec_mod._spec_chunk_sharded(
                            self.params, self.cfg, self._cache,
                            jnp.asarray(self._tok), jnp.asarray(self._pos),
                            jnp.asarray(self._n_out), jnp.asarray(self._done),
                            jnp.asarray(self._hist), jnp.asarray(self._wctr),
                            jnp.asarray(self._acc_ema),
                            jnp.asarray(self._rids), jnp.asarray(self._max_new),
                            jnp.asarray(self._stops), self._key,
                            jnp.float32(temperature), self._extras_slots,
                            mesh=self.mesh, chunk=eff_chunk,
                            page_size=self.page_size, k=eff_k,
                            ngram_n=self.spec.ngram_n, pad_id=self.pad_id,
                            greedy=bool(greedy), top_k=int(top_k),
                            adaptive=self.spec.adaptive,
                            ctrl_alpha=self.spec.ctrl_alpha,
                            accept=self.spec.accept,
                            typical_eps=self.spec.typical_eps,
                            typical_delta=self.spec.typical_delta)
                else:
                    step = (_decode_chunk if self.mesh is None
                            else functools.partial(_decode_chunk_sharded,
                                                   mesh=self.mesh))
                    (self._cache, tok, pos, n_out, done, emits,
                     lives, bad) = step(
                        self.params, self.cfg, self._cache,
                        jnp.asarray(self._tok),
                        jnp.asarray(self._pos), jnp.asarray(self._n_out),
                        jnp.asarray(self._done), jnp.asarray(self._rids),
                        jnp.asarray(self._max_new),
                        jnp.asarray(self._stops), self._key,
                        jnp.float32(temperature), self._extras_slots,
                        chunk=eff_chunk, page_size=self.page_size,
                        greedy=bool(greedy), top_k=int(top_k),
                        pad_id=self.pad_id)
                dispatch.close()
                with self._span("engine.wait", round=rnd):
                    if spec_on:
                        emits, ms = np.asarray(emits), np.asarray(ms)
                    else:
                        emits, lives = np.asarray(emits), np.asarray(lives)
                emit = self._span("engine.emit", round=rnd)
                if ema is not None:
                    self._acc_ema = np.array(ema)
                if not spec_on:
                    self.nonfinite_logits += int(bad)
            except (ChunkFault, EngineCrash):
                raise
            except Exception as e:
                if hardened:
                    # The compiled step died mid-execution (its donated
                    # cache is gone) — surface as a crash: the supervisor
                    # rebuilds everything from the last snapshot.
                    raise EngineCrash(f"chunk execution failed: {e}") from e
                raise
            if spec_on:
                self._hist = np.array(hist)
                self._wctr = np.array(wctr)
                for t in range(eff_chunk):
                    for slot in range(self.slots):
                        mm = int(ms[t, slot])
                        if mm and self._slot_req[slot] >= 0:
                            self._outputs[self._slot_req[slot]].extend(
                                int(x) for x in emits[t, slot, :mm])
                            self.spec_emitted += mm
                            self.spec_live_steps += 1
                if self.debug_check_hist:
                    for slot in range(self.slots):
                        ridx = self._slot_req[slot]
                        if ridx < 0:
                            continue
                        out = self._outputs[ridx]
                        pl = int(self._plen[slot])
                        got = self._hist[slot, pl : pl + len(out)]
                        if not np.array_equal(
                                got, np.asarray(out, np.int32)):
                            raise AssertionError(
                                f"n-gram history desync on slot {slot} "
                                f"(request {ridx}): hist emissions "
                                f"{got.tolist()} != outputs {out}")
            else:
                cnt = n0.copy()
                for t in range(eff_chunk):
                    for slot in range(self.slots):
                        if lives[t, slot] and self._slot_req[slot] >= 0:
                            tv = int(emits[t, slot])
                            self._outputs[self._slot_req[slot]].append(tv)
                            if self.spec is not None:
                                # Ladder degraded a speculative engine to
                                # plain decode this round: keep the n-gram
                                # history warm so re-enabling speculation
                                # proposes from the full stream.
                                hl = int(self._plen[slot]) + cnt[slot]
                                if self.spec.adaptive:
                                    # Free host-side probe: the chance
                                    # the emitted token equals the
                                    # proposer's next guess IS the
                                    # acceptance ``_ctrl_probe`` would
                                    # measure, so plain fallback rounds
                                    # keep the controller learning.
                                    pred = spec_mod.propose_first_host(
                                        self._hist[slot], hl,
                                        self.spec.ngram_n)
                                    al = self.spec.ctrl_alpha
                                    self._acc_ema[slot] = (
                                        (1.0 - al) * self._acc_ema[slot]
                                        + al * float(pred == tv))
                                self._hist[slot, hl] = tv
                                cnt[slot] += 1
            self._tok = np.array(tok)  # np.array: writable host copies
            self._pos = np.array(pos)
            self._n_out = np.array(n_out)
            self._done = np.array(done)
            # Pages the chunk's last step read per slot, its first pos + 1
            # positions (a plain step advances a slot it ran live), before
            # retirements park finished slots at pos 0.
            last = self._pos if spec_on else self._pos - lives[-1]
            kv_pages_read = int(np.sum(last // self.page_size + 1))
            if hardened:
                skew += policy.round_time
            t_end = now()
            emitted = 0
            for slot in live:
                ridx_s = self._slot_req[slot]
                if ridx_s < 0:
                    continue  # preempted during this round's top-up
                n_tok = int(self._n_out[slot] - n0[slot])
                emitted += n_tok
                records[ridx_s].events.append(
                    {"name": "decode", "ts": t_round_start,
                     "dur": t_end - t_round_start, "round": rnd,
                     "tokens": n_tok})
            for slot in range(self.slots):
                if self._slot_req[slot] >= 0 and self._done[slot]:
                    # Per-slot completion at chunk granularity: interpolate
                    # the round's [t_round_start, t_end] span to the LAST
                    # chunk iteration the slot was live in, instead of
                    # stamping every retiring slot with the same round
                    # boundary (see ServeReport.latencies for the residual
                    # quantization).
                    if spec_on:
                        liv = np.flatnonzero(ms[:, slot] > 0)
                    else:
                        liv = np.flatnonzero(lives[:, slot])
                    fin_it = int(liv[-1]) if liv.size else eff_chunk - 1
                    t_slot = t_round_start + (fin_it + 1) / eff_chunk * (
                        t_end - t_round_start)
                    self._finish(requests, records, slot, t_slot)
            report.counters.append(
                {"ts": t_end, "round": rnd,
                 "free_pages": len(self._pool.free),
                 "retained_pages": len(self._pool.lru),
                 "pages_in_use": self.pages_in_use(),
                 "prefix_hit_tokens": self.prefix_hit_tokens,
                 "eff_k": int(eff_k) if spec_on else 0,
                 "queued": len(self._queue),
                 "retries": report.retries,
                 "kv_pages_read": kv_pages_read,
                 "kv_pages_table": self._bt.size})
            # ---- ladder signals + snapshot
            if hardened:
                bad = []
                if report.retries > retries_before:
                    bad.append("retries")
                if self.preemptions > preempt_before:
                    bad.append("preempt")
                if sheds_round:
                    bad.append("shed")
                if (self._pool.available() / max(1, self.num_pages - 1)
                        < ladder.cfg.free_frac):
                    bad.append("pressure")
                if chaos is not None and lag > 0:
                    bad.append("straggle")
                ladder.update(rnd, bool(bad), "+".join(bad))
                if snap_every and rnd % snap_every == 0:
                    self._take_snapshot(records, policy, rnd)
            emit.late["tokens"] = emitted
            emit.close()
            rnd += 1

        self._pool_poisoned = False  # round loop completed normally
        report.rounds = rnd
        report.ladder_trace = list(ladder.trace)
        report.max_ladder_level = max(
            (lvl for _, lvl, _ in ladder.trace), default=0)
        dropped = [i for i, rec in enumerate(records)
                   if rec.status == "pending"]
        if dropped:
            # A still-pending record means the scheduler LOST a request —
            # it was neither finished, shed, nor rejected.  Raising is the
            # only honest outcome; the old unconditional "pending -> done"
            # coercion hid exactly this class of bug.  Hardened production
            # serving may opt back into coercion (strict_pending=False or
            # unset REPRO_STRICT_SERVE) to prefer degraded answers over an
            # exception, and marks the records so the loss is auditable.
            strict = (self.strict_pending if self.strict_pending is not None
                      else os.environ.get("REPRO_STRICT_SERVE", "")
                      not in ("", "0", "false"))
            if strict or not hardened:
                raise RuntimeError(
                    f"scheduler dropped requests {dropped}: still pending "
                    "after the serve loop — every request must end "
                    "done/shed/rejected")
            for i in dropped:
                records[i].status = "done"
                records[i].reason = "coerced-pending"
        report.prefix_hits = self.prefix_hits
        report.prefix_hit_tokens = self.prefix_hit_tokens
        report.prefill_tokens = self.prefill_tokens
        report.cow_forks = self.cow_forks
        report.evictions = self._pool.evictions
        self.assert_quiescent()
        if snap_every:
            self._take_snapshot(records, policy, rnd)
        self.last_report = report
        return report

    def generate(self, prompt_tokens, n_new: int, *,
                 extras: Optional[dict] = None, greedy: bool = True,
                 temperature: float = 1.0, top_k: int = 0, key=None,
                 stop_tokens: Sequence[int] = ()):
        """Old fixed-batch API as a thin wrapper over the scheduler: each
        batch row becomes a Request (row i of ``extras`` — batched like
        ``ServingEngine.generate``'s — becomes its per-request extras);
        rows retiring early are padded with ``pad_id`` to keep the
        (B, n_new) shape."""
        prompts = np.asarray(prompt_tokens, np.int32)
        reqs = [
            Request(prompt=row, max_new=int(n_new),
                    stop_tokens=tuple(stop_tokens),
                    extras=(None if extras is None
                            else jax.tree.map(lambda a: a[i], extras)))
            for i, row in enumerate(prompts)
        ]
        outs = self.serve(reqs, greedy=greedy, temperature=temperature,
                          top_k=top_k, key=key)
        full = np.full((len(reqs), int(n_new)), self.pad_id, np.int32)
        for i, o in enumerate(outs):
            full[i, : len(o)] = o
        return jnp.asarray(full)
