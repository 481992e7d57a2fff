"""Speculative multi-token decode: amortise one weight stream over several
emitted tokens.

The paper's bound — and ``BENCH_decode.json``'s — is weight bytes per token:
every decode step streams the whole quantized tree to emit ONE token.
Speculation proposes ``k`` cheap draft tokens, then runs the target model
ONCE over the ``k+1``-token window (``models.verify_step``) and emits
``accepted + 1`` tokens (the accepted drafts plus one token the verify pass
itself produces) per weight stream.

Three speculation shapes, set on ``SpecConfig``:

* **Fixed linear** (the default): every window proposes exactly ``k``
  tokens.  Wins when acceptance is high, LOSES wall-clock when it is not —
  a (k+1)-token verify window costs more than a decode step, and a random
  workload accepts almost nothing (BENCH_serving.json's 0.85x/0.54x
  motivated the controller below).
* **Adaptive** (``adaptive=True``): a per-request acceptance EMA (carried
  in the compiled scan, snapshot-restored on crash replay) feeds a
  controller that picks the window width each scheduling round by
  maximising expected emitted tokens per window cost over a static bucket
  set {0, 1, 2, 4, ..., k} — ``k_round = argmax_b sum_live(1 + e + ... +
  e^b) / (1 + cost*b)``, composed with the PR 6 degradation ladder as
  ``min(ladder rung, controller)``.  At ``k_round == 0`` speculation gets
  out of the way but keeps learning for free: the fixed engine runs a
  one-token window whose own logits score the would-be first n-gram draft
  (``_ctrl_probe``: ``p_0(d_1)``, or argmax agreement under greedy), while
  the continuous engine dispatches the genuine PLAIN decode chunk and
  probes host-side (``propose_first_host``: the chance the emitted token
  equals the proposer's guess IS ``p_0(d_1)``) — either way the EMA keeps
  tracking the text and speculation re-engages the moment history becomes
  predictable.  Falling back to plain decode when losing is therefore the
  controller's steady state on hostile workloads, not a special mode.
* **Tree** (``tree_fan=F > 0``, n-gram proposer only): each window carries
  F candidate continuations of depth ``k`` sharing the current token as
  root — ``1 + F*k`` nodes verified in ONE pass through the shared-prefix
  tree attention mask of ``models.verify_step(tree=(F, k))`` on dense and
  paged caches alike.  Acceptance picks the best chain (greedy: longest
  matching prefix over chains; sampled: SpecInfer-style sequential head
  elimination + chain descent, ``sampling.tree_reject_sample`` — still
  EXACTLY distribution-preserving), then ``models.tree_relocate`` moves
  the accepted chain's cache rows into the linear layout before commit.

Verification comes in three flavours:

* **Greedy** (``greedy=True`` decode): accept the longest prefix whose
  greedy argmax agrees with the proposals.  An accepted token is by
  construction exactly what non-speculative greedy decode would have
  emitted, so output is TOKEN-IDENTICAL to the baseline — at any fixed,
  adaptive, ladder-degraded, or tree window
  (tests/test_speculative.py, tests/test_adaptive_spec.py).
* **Sampled exact** (``greedy=False``, ``accept="exact"``):
  rejection-sampling verification (``sampling.rejection_sample`` /
  ``tree_reject_sample``): accept proposal ``d_i ~ q_i`` with probability
  ``min(1, p_i(d_i)/q_i(d_i))`` against the target's warped verify
  distribution ``p_i``, resample the first rejection from the normalised
  residual ``max(p_i - q_i, 0)``, and draw the bonus token from the next
  node's distribution when everything is accepted.
* **Typical** (``accept="typical"``): entropy-band acceptance
  (``sampling.typical_accept_sample``) — accept ``d_i`` iff ``p_i(d_i) >
  min(eps, delta * exp(-H(p_i)))``, no rejection residual.  Explicitly
  LOSSY: the output distribution is biased toward the proposer; callers
  opt in for latency.  Linear windows only.

**Exactness contracts.**  Sampled exact speculation leaves the output
distribution of plain sampled decode EXACTLY unchanged for ANY proposal
distribution and ANY window-width schedule — including the adaptive
controller's, because each round's ``k`` is a deterministic function of
already-emitted data, so the accept/residual construction stays ancestral
sampling from ``p`` by induction over windows.  Proposer quality and
controller policy move the acceptance rate (weight streams paid), never
the law of the output.  The test methodology is two-layered
(tests/test_sampled_speculative.py, tests/test_adaptive_spec.py):

* **Seeded exactness** where the algorithm is key-deterministic: the
  per-row ``(base key, request id, counter)`` fold_in discipline
  (``serving.sampling``) makes the same ``key`` produce identical tokens
  across {dense fixed engine, paged continuous engine} x {1, 8 devices},
  across slot assignments/chunk sizes, and across preemption/recompute
  replays — asserted token-for-token.  Both moe archs are in this matrix:
  ``models.moe.moe_apply`` routes per row and combines over the fixed
  top-k axis, so dense and paged cache layouts agree to the last bit
  (tests/helpers.PAGED_BITEXACT_ARCHS).  Two scoped caveats remain:
  (a) logits are a function of the verify WINDOW WIDTH at the ulp level
  for MLA archs (XLA dot shapes) and at capacity level for moe (the
  dispatch capacity depends on the group length), so contracts that
  compare runs with DIFFERENT window schedules — adaptive vs plain,
  ladder-degraded vs clean — are token-exact under greedy but
  distributional under sampling for those archs; (b) tree chains at
  non-zero fan offsets occupy different store columns than a linear run,
  so tree-vs-linear is ulp-close, not bitwise — while chain 0 against an
  equal-width linear window, and tree dense-vs-paged, ARE bitwise
  (scripts/probe_tree_verify.py measures all three).
* **Distributional equivalence** where seeded identity is out of scope
  (different draw counts or window schedules): empirical token histograms
  over thousands of seeded decodes are compared with a pooled-bin
  chi-square homogeneity test at alpha=0.01 (plus a total-variation
  report), per model family (``tests/helpers.histogram_decode`` /
  ``chi_square_homogeneity``).

Two proposers:

* ``mode="ngram"`` — prompt-lookup decoding: match the last ``ngram_n``
  tokens of the row's history (prompt + emissions) against every earlier
  position and propose the ``k`` tokens that followed the most recent
  match (tree mode: the ``F`` most recent matches, one chain each; chain
  0 is always the linear proposer's choice); fall back to repeating the
  last token.  Zero extra parameters, runs inside the compiled program,
  and thrives on the repetitive tails real decodes (and untrained-model
  attractors) produce.  Deterministic, so its ``q`` is a one-hot point
  mass: acceptance degenerates to ``u < p(d)`` and the residual to ``p``
  with the proposal zeroed.  The history buffer is rebuilt WHOLE at every
  admit (fresh, crash-replay resume, and recompute re-admit alike) and
  kept warm through ladder rounds that disable speculation, so proposals
  always see ``prompt + every emission`` (tests/test_adaptive_spec.py
  audits this invariant under chaos).
* ``mode="draft"`` — a small draft model (its own cache) proposes ``k``
  tokens autoregressively — argmax under greedy decode, sampled from its
  own warped distribution ``q_i`` under sampling; its per-step states
  stack across the chain (``models.stack_verify_caches``) and commit once
  at the accepted length with the same ``commit_verify`` machinery as the
  target — no re-sync forward.  On the fixed engine the draft cache is
  dense; on the continuous engine it is a PAGED pool sharing the target's
  block tables (same page ids, its own storage), so draft speculation
  survives admit/retire/preemption like any other per-slot state.  Under
  the adaptive controller a ``k_round == 0`` window still runs ONE draft
  step so the draft cache tracks the emitted stream.

Rollback discipline (see ``models.verify_step``): attention/MLA writes at
rejected positions are dead by masking and rewritten by the next window;
SSM/conv state returns per-step stacked and ``commit_verify`` keeps the
accepted step per row; the paged engine's rejected page writes are
reclaimed the same way (the block tables never move).  Tree windows add
one step: ``models.tree_relocate`` copies the ACCEPTED chain's rows from
their tree columns (``pos + 1 + cf*k .. ``) into the linear columns
before the commit, on both cache layouts — the engines over-provision
``fan*k`` positions past the request frontier so relocation never reads
through the shared trash page.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import (
    commit_verify,
    init_cache,
    prefill,
    tree_relocate,
    verify_step,
)
from repro.models.lm import stack_verify_caches
from repro.serving.sampling import (
    TAG_TOKEN,
    TAG_WINDOW,
    draw_keys,
    rejection_sample,
    sample_rows,
    tree_reject_sample,
    typical_accept_sample,
    warp_logits,
)
from repro.serving.sharded import tree_pspecs


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Static speculation settings (hashable — safe to close over in jit).

    ``k``: proposed tokens per verify step (the window is ``k+1`` wide;
    tree mode: the chain DEPTH, the window is ``1 + tree_fan*k`` wide).
    ``mode``: ``"ngram"`` (prompt-lookup, default) or ``"draft"`` (draft
    model; the engine must hold ``draft_cfg``/``draft_params``).
    ``ngram_n``: match length for the prompt-lookup proposer.

    ``adaptive``: per-request acceptance-EMA controller (module
    docstring); ``ctrl_alpha`` the EMA coefficient, ``ctrl_init`` the
    optimism a fresh request starts with (the default 0.0 starts every
    request at k=0 — plain-decode-cost rounds whose free probe measures
    real acceptance, so hostile traces pay NOTHING for warm-up and
    proposer-friendly ones climb to wide windows within a few rounds),
    ``ctrl_cost`` the modelled marginal cost of one extra window
    position relative to a decode step (the verify window costs
    ``~(1 + ctrl_cost*k)`` decode steps).

    ``tree_fan``: > 0 switches to multi-candidate tree drafts (n-gram
    proposer only; exclusive with ``adaptive`` and ``accept="typical"``).

    ``accept``: ``"exact"`` (rejection sampling, distribution-preserving)
    or ``"typical"`` (entropy-band acceptance, lossy; linear only)."""

    k: int = 4
    mode: str = "ngram"
    ngram_n: int = 2
    adaptive: bool = False
    ctrl_alpha: float = 0.5
    ctrl_init: float = 0.0
    ctrl_cost: float = 0.18
    tree_fan: int = 0
    accept: str = "exact"
    typical_eps: float = 0.3
    typical_delta: float = 0.09

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"speculation needs k >= 1, got {self.k}")
        if self.mode not in ("ngram", "draft"):
            raise ValueError(f"mode must be ngram|draft, got {self.mode!r}")
        if self.ngram_n < 1:
            raise ValueError(f"ngram_n must be >= 1, got {self.ngram_n}")
        if self.accept not in ("exact", "typical"):
            raise ValueError(
                f"accept must be exact|typical, got {self.accept!r}")
        if self.tree_fan < 0:
            raise ValueError(f"tree_fan must be >= 0, got {self.tree_fan}")
        if self.tree_fan:
            if self.mode != "ngram":
                raise ValueError("tree drafts need mode='ngram' (the draft "
                                 "model proposes one chain)")
            if self.adaptive:
                raise ValueError("tree_fan and adaptive are exclusive (the "
                                 "controller schedules linear windows)")
            if self.accept != "exact":
                raise ValueError("tree verification is exact rejection "
                                 "sampling; accept='typical' is linear-only")
        if not 0.0 < self.ctrl_alpha <= 1.0:
            raise ValueError(f"ctrl_alpha in (0, 1], got {self.ctrl_alpha}")
        if self.ctrl_cost <= 0.0:
            raise ValueError(f"ctrl_cost must be > 0, got {self.ctrl_cost}")


def as_spec(speculate) -> SpecConfig:
    """Normalise an engine's ``speculate=`` argument: SpecConfig, or an int
    shorthand for ``SpecConfig(k=...)``."""
    if isinstance(speculate, SpecConfig):
        return speculate
    return SpecConfig(k=int(speculate))


# -------------------------------------------------------------- controller --
def ctrl_buckets(k: int) -> tuple:
    """Static candidate window widths {0, 1, 2, 4, ..., k}: the controller
    re-jits the chunk at most O(log k) times across a whole serve."""
    bs, b = [0], 1
    while b < k:
        bs.append(b)
        b *= 2
    bs.append(k)
    return tuple(dict.fromkeys(bs))


def _ctrl_gain(e, b: int):
    """Expected tokens one window of width ``b`` emits for a slot with
    per-draft acceptance ``e``: the bonus token plus the geometric
    accepted prefix, ``1 + e + e^2 + ... + e^b``."""
    g, p = 1.0 + 0.0 * e, 1.0 + 0.0 * e
    for _ in range(b):
        p = p * e
        g = g + p
    return g


def adaptive_k_host(ema: np.ndarray, live: np.ndarray,
                    spec: SpecConfig) -> int:
    """The scheduling round's window width: maximise the batch's expected
    emitted tokens per window cost over the bucket set.  Ties (and the
    empty batch) resolve to the SMALLER width — the conservative side of
    the wall-clock bet.  Host-side numpy; the fixed engine runs the jnp
    twin ``_ctrl_k`` inside its loop."""
    if not bool(np.any(live)):
        return 0
    e = np.clip(ema[live].astype(np.float64), 0.0, 1.0)
    best_s, best_b = -1.0, 0
    for b in ctrl_buckets(spec.k):
        s = float(np.sum(_ctrl_gain(e, b))) / (1.0 + spec.ctrl_cost * b)
        if s > best_s + 1e-12:
            best_s, best_b = s, b
    return best_b


def _ctrl_k(ema, live, k: int, cost: float):
    """jnp twin of ``adaptive_k_host`` (traced scalar int32): argmax picks
    the FIRST maximum, i.e. the smallest bucket on ties."""
    buckets = ctrl_buckets(k)
    e = jnp.where(live, jnp.clip(ema, 0.0, 1.0), 0.0)
    scores = jnp.stack([jnp.sum(jnp.where(live, _ctrl_gain(e, b), 0.0))
                        / (1.0 + cost * b) for b in buckets])
    return jnp.asarray(buckets, jnp.int32)[jnp.argmax(scores)]


def _ctrl_probe(lg0, d1, *, greedy: bool, temperature, top_k: int):
    """Free acceptance probe from a window's own first-node logits: the
    probability the would-be first draft ``d1`` would have been accepted
    (point-mass proposal: exactly ``p_0(d_1)``; greedy: argmax
    agreement).  This is what lets a ``k == 0`` round keep learning at
    plain-decode cost."""
    if greedy:
        return (jnp.argmax(lg0, axis=-1).astype(jnp.int32)
                == d1).astype(jnp.float32)
    p0 = jax.nn.softmax(warp_logits(lg0, temperature, top_k), axis=-1)
    return jnp.take_along_axis(p0, d1[:, None], axis=1)[:, 0]


def _ctrl_update(ema, live, a, k_window, phat0, alpha: float):
    """One EMA step from this window's observation: with a real window,
    the censored-geometric estimate ``a/(a+1)`` (1.0 when every proposal
    was accepted); at width 0, the free probe.  Done slots freeze."""
    af = a.astype(jnp.float32)
    kw = jnp.asarray(k_window, jnp.int32)
    r = jnp.where(kw == 0, phat0,
                  jnp.where(a >= kw, 1.0, af / (af + 1.0)))
    return jnp.where(live, (1.0 - alpha) * ema + alpha * r, ema)


# ---------------------------------------------------------------- proposer --
def propose_ngram(hist: jnp.ndarray, hlen: jnp.ndarray, k: int,
                  n: int) -> jnp.ndarray:
    """Prompt-lookup proposal: for each row of ``hist`` (B, W) with live
    length ``hlen`` (B,) — prompt plus every emitted token, the last one
    still pending — find the most recent earlier occurrence of the trailing
    ``n``-gram and propose the ``k`` tokens that followed it.  Positions
    past the match's continuation (and rows with no match) propose the last
    token — a cheap guess that costs nothing when rejected.  Returns
    (B, k) int32."""
    j, last = _ngram_matches(hist, hlen, 1, n)
    found = j[:, 0] >= 0
    src = j + n + jnp.arange(k)[None, :]  # (B, k)
    prop = jnp.take_along_axis(hist, jnp.clip(src, 0, hist.shape[1] - 1),
                               axis=1)
    use = found[:, None] & (src < hlen[:, None])
    return jnp.where(use, prop, last).astype(jnp.int32)


def propose_first_host(hist_row: np.ndarray, hlen: int, n: int) -> int:
    """Host/numpy twin of ``propose_ngram``'s FIRST proposed token for one
    row: the token following the most recent earlier occurrence of the
    trailing ``n``-gram, falling back to repeating the last token.  The
    adaptive controller's plain-decode fallback rounds probe with it at
    zero device cost: for sampled decode ``P(emitted == proposal)`` is
    exactly ``p0(proposal)`` — the quantity ``_ctrl_probe`` measures
    on-device — and for greedy decode the indicator IS the
    argmax-agreement probe."""
    if hlen >= n + 1:
        h = hist_row[:hlen]
        gram = h[hlen - n:]
        win = np.lib.stride_tricks.sliding_window_view(h, n)
        hits = np.nonzero((win[: hlen - n] == gram).all(axis=1))[0]
        if hits.size:
            return int(h[hits[-1] + n])
    return int(hist_row[max(hlen - 1, 0)])


def _ngram_matches(hist, hlen, fan: int, n: int):
    """Positions of the ``fan`` most recent earlier occurrences of each
    row's trailing ``n``-gram, descending (most recent first; -1 where
    fewer exist), plus the last-token fallback.  Returns (j (B, fan),
    last (B, 1))."""
    b, w = hist.shape
    gi = hlen[:, None] - n + jnp.arange(n)[None, :]
    gram = jnp.take_along_axis(hist, jnp.clip(gi, 0, w - 1), axis=1)  # (B, n)
    match = jnp.ones((b, w), bool)
    for i in range(n):
        # window starting at q sees hist[q + i]; shift left, pad invalid
        shifted = jnp.pad(hist[:, i:], ((0, 0), (0, i)), constant_values=-1)
        match = match & (shifted == gram[:, i : i + 1])
    q = jnp.arange(w)[None, :]
    # strictly-earlier windows only: the trailing gram itself sits at
    # hlen - n, so candidates end at hlen - n - 1
    valid = match & (q <= hlen[:, None] - n - 1)
    scored = jnp.where(valid, q, -1)
    j = jax.lax.top_k(scored, fan)[0]  # (B, fan) most recent first
    last = jnp.take_along_axis(hist, jnp.clip(hlen - 1, 0, w - 1)[:, None],
                               axis=1)  # (B, 1)
    return j, last


def propose_ngram_tree(hist: jnp.ndarray, hlen: jnp.ndarray, fan: int,
                       depth: int, n: int) -> jnp.ndarray:
    """Multi-candidate prompt-lookup: one chain per earlier occurrence of
    the trailing n-gram, most recent first — chain 0 is exactly
    ``propose_ngram``'s choice, so a 1-fan tree degenerates to the linear
    proposer.  Rows (or trailing chains) without a match fall back to
    repeating the last token; duplicate chains are harmless — sampled
    verification auto-rejects a head whose mass was already consumed, and
    greedy takes the longest prefix wherever it appears.  Returns
    (B, fan, depth) int32."""
    b, w = hist.shape
    j, last = _ngram_matches(hist, hlen, fan, n)
    found = j >= 0  # (B, fan)
    src = j[:, :, None] + n + jnp.arange(depth)[None, None, :]  # (B, F, D)
    prop = jnp.take_along_axis(
        hist, jnp.clip(src, 0, w - 1).reshape(b, fan * depth), axis=1
    ).reshape(b, fan, depth)
    use = found[:, :, None] & (src < hlen[:, None, None])
    return jnp.where(use, prop, last[:, :, None]).astype(jnp.int32)


# -------------------------------------------------------------- acceptance --
def greedy_accept(window: jnp.ndarray, logits: jnp.ndarray):
    """Longest-matching-prefix greedy acceptance.  ``window`` (B, k+1) is
    the verified input (last accepted token + k proposals); ``logits``
    (B, k+1, V) the target's outputs.  Returns ``(g, a)``: the target's
    greedy tokens (B, k+1) — position j is the token following window[:j+1]
    — and ``a`` (B,) the number of accepted proposals; the row emits
    ``g[:a+1]`` (accepted proposals == g[:a] plus the free bonus token)."""
    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    match = (window[:, 1:] == g[:, :-1]).astype(jnp.int32)
    a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    return g, a


def greedy_tree_accept(chains: jnp.ndarray, logits: jnp.ndarray, *,
                       kcap=None):
    """Greedy acceptance over a fan-of-chains tree: per chain, the longest
    prefix whose tokens equal the argmax at their predecessor node; the
    window keeps the best chain (ties: lowest index, which is the linear
    proposer's chain).  ``chains`` (B, F, D); ``logits`` (B, 1+F*D, V) in
    node order.  Returns ``(tokens (B, D+1), a (B,), cf (B,))`` laid out
    like ``sampling.tree_reject_sample``: the row emits
    ``tokens[:, :a+1]``, the last of which is the bonus argmax at the
    deepest accepted node."""
    b, fan, depth = chains.shape
    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, 1+F*D)
    # chain f step i's predecessor node: the root for i == 0, else the
    # previous step 1 + f*depth + (i-1) == f*depth + i.
    pred = np.zeros((fan, depth), np.int32)
    for f in range(fan):
        for i in range(1, depth):
            pred[f, i] = f * depth + i
    match = (chains == g[:, jnp.asarray(pred)]).astype(jnp.int32)  # (B,F,D)
    af = jnp.sum(jnp.cumprod(match, axis=2), axis=2)  # (B, F)
    if kcap is not None:
        af = jnp.minimum(af, kcap[:, None])
    a = jnp.max(af, axis=1)
    cf = jnp.argmax(af, axis=1).astype(jnp.int32)  # first max: lowest f
    ch = jnp.take_along_axis(chains, cf[:, None, None], axis=1)[:, 0]  # (B,D)
    last_node = jnp.where(a > 0, cf * depth + a, 0)
    bonus = jnp.take_along_axis(g, last_node[:, None], axis=1)  # (B, 1)
    padded = jnp.concatenate([ch, ch[:, -1:]], axis=1)
    toks = jnp.where(jnp.arange(depth + 1)[None, :] < a[:, None],
                     padded, bonus)
    return toks, a, cf


def _accept(window, drafts, lg, *, greedy: bool, temperature, top_k: int,
            wkeys, q, kcap=None, n_draws=None, accept: str = "exact",
            typical_eps: float = 0.3, typical_delta: float = 0.09):
    """One linear-window verification: greedy longest-prefix, rejection
    sampling against the warped target distribution, or typical
    (entropy-band) acceptance.  Returns ``(g, a)`` with the shared
    contract that the row emits ``g[:, :a+1]``.  ``q`` is the proposal
    distribution (B, k, V) or None for deterministic proposers (one-hot
    point mass).  ``kcap``/``n_draws`` implement the fixed engine's
    adaptive cap: the window stays ``k`` wide (static shapes) while
    acceptance stops at the controller's width, with a cap-independent
    draw stream.  A zero-width window (``drafts`` (B, 0)) degenerates to
    one plain draw — greedy argmax, or a categorical on the window key's
    final-draw half, mirroring ``rejection_sample``'s ``kcap == 0``
    stream."""
    if greedy:
        g, a = greedy_accept(window, lg)
        if kcap is not None:
            a = jnp.minimum(a, kcap)
        return g, a
    if drafts.shape[1] == 0:
        kf = jax.vmap(lambda kk: jax.random.split(kk)[1])(wkeys)
        wl = warp_logits(lg[:, 0], temperature, top_k)
        t0 = jax.vmap(jax.random.categorical)(kf, wl).astype(jnp.int32)
        return t0[:, None], jnp.zeros((lg.shape[0],), jnp.int32)
    p = jax.nn.softmax(warp_logits(lg, temperature, top_k), axis=-1)
    if accept == "typical":
        return typical_accept_sample(wkeys, drafts, p, kcap=kcap,
                                     eps=typical_eps, delta=typical_delta)
    if q is None:
        q = jax.nn.one_hot(drafts, lg.shape[-1], dtype=jnp.float32)
    return rejection_sample(wkeys, drafts, q, p, kcap=kcap, n_draws=n_draws)


# ------------------------------------------------- fixed-batch spec engine --
def _draft_propose(draft_params, draft_cfg, dcache, tok, pos, extras, k,
                   *, page_size: int = 0, wkeys=None, greedy: bool = True,
                   temperature=1.0, top_k: int = 0):
    """Autoregressive draft proposals: k+1 single-token steps consume the
    whole window ``[tok, d_1..d_k]`` (the extra step eats ``d_k`` so every
    accepted length has a state).  Greedy decode proposes the draft's
    argmax; sampled decode draws ``d_i ~ q_i`` from the draft's warped
    distribution using per-row subkeys of the window key, and returns the
    stacked ``q`` (B, k, V) for the rejection-sampling accept ratio.
    Returns ``(drafts (B, k), q or None, stacked)`` where ``stacked`` is
    the chain's states merged into one verify cache
    (``models.stack_verify_caches``) — the caller commits it once at the
    accepted length, no re-sync forward.  With a paged ``dcache`` (the
    continuous engine) the chain scatters/gathers through the draft pool's
    block tables at per-slot positions.  ``k == 0`` (an adaptive
    plain-decode round) still runs the single step that consumes ``tok``,
    so the draft cache keeps tracking the emitted stream."""
    dc, t, ds, qs, vcs = dcache, tok, [], [], []
    zero = jnp.zeros((tok.shape[0],), jnp.int32)
    for i in range(k + 1):
        lg, vc = verify_step(draft_params, draft_cfg, t, dc, pos + i, extras,
                             page_size=page_size)
        vcs.append(vc)
        dc = commit_verify(draft_cfg, vc, zero)
        if i < k:
            last = lg[:, -1, :]
            if greedy:
                t = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
            else:
                wl = warp_logits(last, temperature, top_k)
                ki = jax.vmap(lambda kk: jax.random.fold_in(kk, 3 + i))(wkeys)
                t = jax.vmap(jax.random.categorical)(ki, wl).astype(
                    jnp.int32)[:, None]
                qs.append(jax.nn.softmax(wl, axis=-1))
            ds.append(t)
    drafts = (jnp.concatenate(ds, axis=1) if ds
              else jnp.zeros((tok.shape[0], 0), jnp.int32))
    return (drafts, jnp.stack(qs, axis=1) if qs else None,
            stack_verify_caches(draft_cfg, vcs))


def _spec_generate_body(params, cfg: ModelConfig, prompt, extras, draft_params,
                        key, temperature, *, draft_cfg, n_new: int,
                        max_seq: int, k: int, mode: str, ngram_n: int,
                        greedy: bool, top_k: int, adaptive: bool = False,
                        ctrl_alpha: float = 0.5, ctrl_init: float = 0.5,
                        ctrl_cost: float = 0.18, accept: str = "exact",
                        typical_eps: float = 0.3,
                        typical_delta: float = 0.09):
    """Whole speculative generation — prefill + a verify-window loop — as
    one XLA program.  Greedy verification, rejection sampling, or typical
    acceptance (see module docstring).  With ``adaptive=True`` the loop
    carries the per-row acceptance EMA and caps acceptance at the
    controller's width each iteration; the WINDOW stays ``k`` wide (a
    fixed batch cannot reshape a compiled loop), so the fixed engine is
    the controller's reference semantics — the wall-clock savings live in
    the continuous engine, which actually narrows the window.  Returns
    (tokens (B, n_new), verify_steps, live_row_steps): greedy tokens are
    identical to the plain greedy ``generate``; sampled tokens are
    key-deterministic (per-row fold_in streams) and distributionally
    identical to plain sampled decode.  emitted-per-live-row-step =
    ``B*(n_new-1) / live_row_steps`` is the speculation multiplier."""
    b, s = prompt.shape
    if n_new == 0:
        return (jnp.zeros((b, 0), jnp.int32), jnp.int32(0), jnp.int32(0))
    rids = jnp.arange(b, dtype=jnp.int32)
    cache = init_cache(cfg, b, max_seq)
    logits, cache = prefill(params, cfg, prompt, cache, extras)
    tok = sample_rows(
        logits[:, -1, :],
        None if greedy else draw_keys(key, rids, 0, TAG_TOKEN),
        greedy=greedy, temperature=temperature, top_k=top_k)[:, None]
    hist = jnp.zeros((b, max_seq), jnp.int32)
    hist = jax.lax.dynamic_update_slice(hist, prompt.astype(jnp.int32), (0, 0))
    hist = hist.at[:, s].set(tok[:, 0])
    out = jnp.zeros((b, n_new), jnp.int32).at[:, 0].set(tok[:, 0])
    n_em = jnp.ones((b,), jnp.int32)
    if mode == "draft":
        # k extra positions: the draft chain reads back the speculative
        # positions it writes, and near the max_seq frontier those reads
        # must hit real stored values (a dense out-of-store write DROPS),
        # mirroring the paged engine's _store_seq over-provisioning so the
        # two engines stay key-identical at the boundary.
        dcache = init_cache(draft_cfg, b, max_seq + k)
        _, dcache = prefill(draft_params, draft_cfg, prompt, dcache, extras)
    else:
        dcache = ()
    rows = jnp.arange(b)[:, None]
    steps0 = jnp.int32(0)
    wctr0 = jnp.zeros((b,), jnp.int32)
    ema0 = jnp.full((b,), ctrl_init, jnp.float32)

    def cond(carry):
        return jnp.any(carry[3] < n_new)

    def body(carry):
        (tok, cache, dcache, n_em, out, hist, wctr, ema, steps,
         live_steps) = carry
        pos = jnp.int32(s) - 1 + n_em  # (B,) tokens already consumed
        live = n_em < n_new
        wkeys = (None if greedy
                 else draw_keys(key, rids, wctr, TAG_WINDOW))
        if mode == "draft":
            drafts, q, dstack = _draft_propose(
                draft_params, draft_cfg, dcache, tok, pos, extras, k,
                wkeys=wkeys, greedy=greedy, temperature=temperature,
                top_k=top_k)
        else:
            drafts = propose_ngram(hist, jnp.int32(s) + n_em, k, ngram_n)
            q = None
        window = jnp.concatenate([tok, drafts], axis=1)  # (B, k+1)
        lg, vc = verify_step(params, cfg, window, cache, pos, extras)
        if adaptive:
            keff = _ctrl_k(ema, live, k, ctrl_cost)
            kcap = jnp.broadcast_to(keff, (b,))
        else:
            keff, kcap = jnp.int32(k), None
        g, a = _accept(window, drafts, lg, greedy=greedy,
                       temperature=temperature, top_k=top_k, wkeys=wkeys,
                       q=q, kcap=kcap, n_draws=k, accept=accept,
                       typical_eps=typical_eps, typical_delta=typical_delta)
        if adaptive:
            d1 = (drafts[:, 0] if mode == "draft"
                  else propose_ngram(hist, jnp.int32(s) + n_em, 1,
                                     ngram_n)[:, 0])
            phat0 = _ctrl_probe(lg[:, 0], d1, greedy=greedy,
                                temperature=temperature, top_k=top_k)
            ema = _ctrl_update(ema, live, a, keff, phat0, ctrl_alpha)
        m = jnp.where(live, jnp.minimum(a + 1, n_new - n_em), 0)  # (B,)
        emit = jnp.arange(k + 1)[None, :] < m[:, None]
        cols = n_em[:, None] + jnp.arange(k + 1)[None, :]
        out = out.at[rows, jnp.where(emit, cols, n_new)].set(g, mode="drop")
        hist = hist.at[rows, jnp.where(emit, jnp.int32(s) + cols, max_seq)
                       ].set(g, mode="drop")
        cache = commit_verify(cfg, vc, jnp.maximum(m - 1, 0))
        if mode == "draft":
            dcache = commit_verify(draft_cfg, dstack, jnp.maximum(m - 1, 0))
        tok = jnp.where((m > 0)[:, None],
                        jnp.take_along_axis(g, jnp.maximum(m - 1, 0)[:, None],
                                            axis=1),
                        tok)
        n_em = n_em + m
        return (tok, cache, dcache, n_em, out, hist,
                wctr + live.astype(jnp.int32), ema, steps + 1,
                live_steps + jnp.sum(live.astype(jnp.int32)))

    carry = jax.lax.while_loop(
        cond, body,
        (tok, cache, dcache, n_em, out, hist, wctr0, ema0, steps0, steps0))
    return carry[4], carry[8], carry[9]


_spec_generate = functools.partial(
    jax.jit,
    static_argnames=("cfg", "draft_cfg", "n_new", "max_seq", "k", "mode",
                     "ngram_n", "greedy", "top_k", "adaptive", "ctrl_alpha",
                     "ctrl_init", "ctrl_cost", "accept", "typical_eps",
                     "typical_delta"),
)(_spec_generate_body)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "n_new", "max_seq", "k", "ngram_n",
                     "greedy", "top_k", "adaptive", "ctrl_alpha", "ctrl_init",
                     "ctrl_cost", "accept", "typical_eps", "typical_delta"),
)
def _spec_generate_sharded(params, cfg: ModelConfig, prompt, extras, key,
                           temperature, *, mesh, n_new: int, max_seq: int,
                           k: int, ngram_n: int, greedy: bool, top_k: int,
                           adaptive: bool = False, ctrl_alpha: float = 0.5,
                           ctrl_init: float = 0.5, ctrl_cost: float = 0.18,
                           accept: str = "exact", typical_eps: float = 0.3,
                           typical_delta: float = 0.09):
    """``_spec_generate_body`` (ngram mode) under ``shard_map``: weight
    shards per device, everything else — including the PRNG key and the
    controller EMA — is replicated, so every device draws the same samples
    and iterates in lockstep."""

    def f(p, pr, ex, ky, t):
        return _spec_generate_body(
            p, cfg, pr, ex, None, ky, t, draft_cfg=None, n_new=n_new,
            max_seq=max_seq, k=k, mode="ngram", ngram_n=ngram_n,
            greedy=greedy, top_k=top_k, adaptive=adaptive,
            ctrl_alpha=ctrl_alpha, ctrl_init=ctrl_init, ctrl_cost=ctrl_cost,
            accept=accept, typical_eps=typical_eps,
            typical_delta=typical_delta)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(tree_pspecs(params), P(), P(), P(), P()),
        out_specs=(P(), P(), P()), check_vma=False,
    )(params, prompt, extras, key, temperature)


def _spec_tree_generate_body(params, cfg: ModelConfig, prompt, extras, key,
                             temperature, *, n_new: int, max_seq: int,
                             fan: int, depth: int, ngram_n: int,
                             greedy: bool, top_k: int):
    """Tree-draft generation on the fixed dense engine: each iteration
    verifies a ``1 + fan*depth``-node window (``verify_step(tree=...)``),
    accepts the best chain, relocates its cache rows into the linear
    layout (``models.tree_relocate``), and commits the matching SSM node.
    The dense store carries ``fan*depth`` columns past ``max_seq`` so
    relocation near the frontier always reads real rows."""
    b, s = prompt.shape
    if n_new == 0:
        return (jnp.zeros((b, 0), jnp.int32), jnp.int32(0), jnp.int32(0))
    rids = jnp.arange(b, dtype=jnp.int32)
    t_nodes = 1 + fan * depth
    cache = init_cache(cfg, b, max_seq + fan * depth)
    logits, cache = prefill(params, cfg, prompt, cache, extras)
    tok = sample_rows(
        logits[:, -1, :],
        None if greedy else draw_keys(key, rids, 0, TAG_TOKEN),
        greedy=greedy, temperature=temperature, top_k=top_k)[:, None]
    hist = jnp.zeros((b, max_seq), jnp.int32)
    hist = jax.lax.dynamic_update_slice(hist, prompt.astype(jnp.int32), (0, 0))
    hist = hist.at[:, s].set(tok[:, 0])
    out = jnp.zeros((b, n_new), jnp.int32).at[:, 0].set(tok[:, 0])
    n_em = jnp.ones((b,), jnp.int32)
    rows = jnp.arange(b)[:, None]
    steps0 = jnp.int32(0)
    wctr0 = jnp.zeros((b,), jnp.int32)

    def cond(carry):
        return jnp.any(carry[2] < n_new)

    def body(carry):
        tok, cache, n_em, out, hist, wctr, steps, live_steps = carry
        pos = jnp.int32(s) - 1 + n_em
        live = n_em < n_new
        wkeys = (None if greedy
                 else draw_keys(key, rids, wctr, TAG_WINDOW))
        chains = propose_ngram_tree(hist, jnp.int32(s) + n_em, fan, depth,
                                    ngram_n)
        window = jnp.concatenate([tok, chains.reshape(b, fan * depth)],
                                 axis=1)  # (B, 1+F*D)
        lg, vc = verify_step(params, cfg, window, cache, pos, extras,
                             tree=(fan, depth))
        if greedy:
            g, a, cf = greedy_tree_accept(chains, lg)
        else:
            p = jax.nn.softmax(warp_logits(lg, temperature, top_k), axis=-1)
            g, a, cf = tree_reject_sample(wkeys, chains, p)
        m = jnp.where(live, jnp.minimum(a + 1, n_new - n_em), 0)
        acc = jnp.maximum(m - 1, 0)  # accepted drafts actually kept
        emit = jnp.arange(depth + 1)[None, :] < m[:, None]
        cols = n_em[:, None] + jnp.arange(depth + 1)[None, :]
        out = out.at[rows, jnp.where(emit, cols, n_new)].set(g, mode="drop")
        hist = hist.at[rows, jnp.where(emit, jnp.int32(s) + cols, max_seq)
                       ].set(g, mode="drop")
        vc = tree_relocate(cfg, vc, pos, acc, cf, fan=fan, depth=depth)
        sel = jnp.where(acc > 0, cf * depth + acc, 0)  # deepest kept node
        cache = commit_verify(cfg, vc, sel)
        tok = jnp.where((m > 0)[:, None],
                        jnp.take_along_axis(g, acc[:, None], axis=1),
                        tok)
        n_em = n_em + m
        return (tok, cache, n_em, out, hist,
                wctr + live.astype(jnp.int32), steps + 1,
                live_steps + jnp.sum(live.astype(jnp.int32)))

    carry = jax.lax.while_loop(
        cond, body, (tok, cache, n_em, out, hist, wctr0, steps0, steps0))
    del t_nodes
    return carry[3], carry[6], carry[7]


_spec_tree_generate = functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_new", "max_seq", "fan", "depth", "ngram_n",
                     "greedy", "top_k"),
)(_spec_tree_generate_body)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "n_new", "max_seq", "fan", "depth",
                     "ngram_n", "greedy", "top_k"),
)
def _spec_tree_generate_sharded(params, cfg: ModelConfig, prompt, extras, key,
                                temperature, *, mesh, n_new: int,
                                max_seq: int, fan: int, depth: int,
                                ngram_n: int, greedy: bool, top_k: int):
    """``_spec_tree_generate_body`` under ``shard_map`` (weight shards per
    device, replicated everything else)."""

    def f(p, pr, ex, ky, t):
        return _spec_tree_generate_body(
            p, cfg, pr, ex, ky, t, n_new=n_new, max_seq=max_seq, fan=fan,
            depth=depth, ngram_n=ngram_n, greedy=greedy, top_k=top_k)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(tree_pspecs(params), P(), P(), P(), P()),
        out_specs=(P(), P(), P()), check_vma=False,
    )(params, prompt, extras, key, temperature)


# ------------------------------------------- continuous-batching spec chunk --
def _spec_chunk_body(params, cfg: ModelConfig, cache, draft_params, dcache,
                     tok, pos, n_out, done, hist, wctr, ema, rids, max_new,
                     stops, key, temperature, extras, *, draft_cfg,
                     chunk: int, page_size: int, k: int, mode: str,
                     ngram_n: int, pad_id: int, greedy: bool, top_k: int,
                     adaptive: bool, ctrl_alpha: float, accept: str,
                     typical_eps: float, typical_delta: float):
    """``chunk`` speculative verify windows over all batch slots as one
    compiled scan — the speculation analogue of ``engine._decode_chunk_body``
    (greedy, rejection-sampled, or typical-accepted).  Each iteration
    proposes ``k`` tokens per slot (n-gram history lookup, or the paged
    draft model), verifies the window against the paged cache, and
    advances each slot by its own accepted length (done slots advance 0
    and write only their own pages or the trash page).  ``k`` here is the
    ROUND's width — under the adaptive controller the host re-picks it
    from the returned per-slot acceptance EMAs at every chunk boundary
    (``adaptive_k_host``), down to ``k == 0``: a one-token window at
    plain-decode cost that still probes the would-be first draft
    (``_ctrl_probe``) so the EMA can recover.  Sampled draws are keyed
    per slot by ``(key, rid, window counter)`` so slot assignment and
    chunk boundaries never change a request's stream.  Emissions are
    truncated at the slot's first stop token and at ``max_new``.  Returns
    per-iteration ``emits`` (chunk, B, k+1) and counts ``ms`` (chunk, B)
    — the host appends ``emits[t, s, :ms[t, s]]``."""
    b = tok.shape[0]
    rows = jnp.arange(b)[:, None]

    def body(carry, _):
        tok, cache, dcache, pos, n_out, done, hist, wctr, ema = carry
        live = ~done
        wkeys = (None if greedy
                 else draw_keys(key, rids, wctr, TAG_WINDOW))
        props = propose_ngram(hist, pos + 1, max(k, 1), ngram_n)
        if mode == "draft":
            drafts, q, dstack = _draft_propose(
                draft_params, draft_cfg, dcache, tok, pos, extras, k,
                page_size=page_size, wkeys=wkeys, greedy=greedy,
                temperature=temperature, top_k=top_k)
        else:
            drafts = props[:, :k]
            q = None
        window = jnp.concatenate([tok, drafts], axis=1)
        lg, vc = verify_step(params, cfg, window, cache, pos, extras,
                             page_size=page_size)
        g, a = _accept(window, drafts, lg, greedy=greedy,
                       temperature=temperature, top_k=top_k, wkeys=wkeys,
                       q=q, accept=accept, typical_eps=typical_eps,
                       typical_delta=typical_delta)
        if adaptive:
            d1 = drafts[:, 0] if (mode == "draft" and k) else props[:, 0]
            phat0 = _ctrl_probe(lg[:, 0], d1, greedy=greedy,
                                temperature=temperature, top_k=top_k)
            ema = _ctrl_update(ema, live, a, k, phat0, ctrl_alpha)
        m = jnp.minimum(a + 1, max_new - n_out)
        # A stop token accepted mid-window truncates the window THERE: the
        # stop itself is emitted, everything after it in the window is
        # masked (never reaches the output, the history, or `tok`).
        hit = jnp.any(g[:, :, None] == stops[:, None, :], axis=-1)  # (B, k+1)
        hitm = hit & (jnp.arange(k + 1)[None, :] < m[:, None])
        any_hit = jnp.any(hitm, axis=1)
        first = jnp.argmax(hitm.astype(jnp.int32), axis=1)
        m = jnp.where(any_hit, first + 1, m)
        m = jnp.where(live, m, 0)
        emit_mask = jnp.arange(k + 1)[None, :] < m[:, None]
        emit = jnp.where(emit_mask, g, jnp.int32(pad_id))
        histcol = pos[:, None] + 1 + jnp.arange(k + 1)[None, :]
        hist = hist.at[rows, jnp.where(emit_mask, histcol, hist.shape[1])
                       ].set(g, mode="drop")
        tok = jnp.where((m > 0)[:, None],
                        jnp.take_along_axis(g, jnp.maximum(m - 1, 0)[:, None],
                                            axis=1),
                        tok)
        pos = pos + m
        n_out = n_out + m
        done = done | (live & any_hit) | (n_out >= max_new)
        cache = commit_verify(cfg, vc, jnp.maximum(m - 1, 0))
        if mode == "draft":
            dcache = commit_verify(draft_cfg, dstack, jnp.maximum(m - 1, 0))
        return ((tok, cache, dcache, pos, n_out, done, hist,
                 wctr + live.astype(jnp.int32), ema), (emit, m))

    carry, (emits, ms) = jax.lax.scan(
        body, (tok, cache, dcache, pos, n_out, done, hist, wctr, ema), None,
        length=chunk)
    tok, cache, dcache, pos, n_out, done, hist, wctr, ema = carry
    return (cache, dcache, tok, pos, n_out, done, hist, wctr, ema, emits,
            ms)


_spec_chunk = functools.partial(
    jax.jit,
    static_argnames=("cfg", "draft_cfg", "chunk", "page_size", "k", "mode",
                     "ngram_n", "pad_id", "greedy", "top_k", "adaptive",
                     "ctrl_alpha", "accept", "typical_eps", "typical_delta"),
    donate_argnames=("cache", "dcache"),
)(_spec_chunk_body)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "chunk", "page_size", "k", "ngram_n",
                     "pad_id", "greedy", "top_k", "adaptive", "ctrl_alpha",
                     "accept", "typical_eps", "typical_delta"),
    donate_argnames=("cache",),
)
def _spec_chunk_sharded(params, cfg: ModelConfig, cache, tok, pos, n_out,
                        done, hist, wctr, ema, rids, max_new, stops, key,
                        temperature, extras, *, mesh, chunk: int,
                        page_size: int, k: int, ngram_n: int, pad_id: int,
                        greedy: bool, top_k: int, adaptive: bool,
                        ctrl_alpha: float, accept: str, typical_eps: float,
                        typical_delta: float):
    """``_spec_chunk_body`` (ngram mode) under ``shard_map`` (weight shards
    per device; paged pools, history, PRNG key, controller EMA, and
    scheduler carry replicated — every device draws identical samples)."""

    def f(p, c, tk, ps_, no, dn, hs, wc, em, ri, mn, st, ky, t, ex):
        (c, _, tk, ps_, no, dn, hs, wc, em, emits, ms) = _spec_chunk_body(
            p, cfg, c, None, (), tk, ps_, no, dn, hs, wc, em, ri, mn, st,
            ky, t, ex, draft_cfg=None, chunk=chunk, page_size=page_size,
            k=k, mode="ngram", ngram_n=ngram_n, pad_id=pad_id, greedy=greedy,
            top_k=top_k, adaptive=adaptive, ctrl_alpha=ctrl_alpha,
            accept=accept, typical_eps=typical_eps,
            typical_delta=typical_delta)
        return c, tk, ps_, no, dn, hs, wc, em, emits, ms

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(tree_pspecs(params),) + (P(),) * 14,
        out_specs=P(), check_vma=False,
    )(params, cache, tok, pos, n_out, done, hist, wctr, ema, rids, max_new,
      stops, key, temperature, extras)


def _spec_tree_chunk_body(params, cfg: ModelConfig, cache, tok, pos, n_out,
                          done, hist, wctr, rids, max_new, stops, key,
                          temperature, extras, *, chunk: int, page_size: int,
                          fan: int, depth: int, ngram_n: int, pad_id: int,
                          greedy: bool, top_k: int):
    """Tree-draft decode chunk on the paged cache: each iteration verifies
    a ``1 + fan*depth``-node window per slot (shared-prefix tree mask,
    ``models.verify_step(tree=...)``), accepts the best chain
    (``greedy_tree_accept`` / ``sampling.tree_reject_sample``), relocates
    the accepted chain's rows from their tree columns into the linear
    layout through the block tables (``models.tree_relocate``), and
    commits the deepest kept SSM node.  The engine over-provisions
    ``fan*depth`` positions past the request frontier so relocation's
    gathers always hit provisioned pages (a trash-page gather would
    corrupt committed positions, not just degrade proposals).  Emission,
    stop truncation, history, and key discipline are identical to the
    linear ``_spec_chunk_body``; ``depth`` here is the ROUND's depth
    (the degradation ladder may halve it)."""
    b = tok.shape[0]
    rows = jnp.arange(b)[:, None]

    def body(carry, _):
        tok, cache, pos, n_out, done, hist, wctr = carry
        live = ~done
        wkeys = (None if greedy
                 else draw_keys(key, rids, wctr, TAG_WINDOW))
        chains = propose_ngram_tree(hist, pos + 1, fan, depth, ngram_n)
        window = jnp.concatenate([tok, chains.reshape(b, fan * depth)],
                                 axis=1)
        lg, vc = verify_step(params, cfg, window, cache, pos, extras,
                             page_size=page_size, tree=(fan, depth))
        if greedy:
            g, a, cf = greedy_tree_accept(chains, lg)
        else:
            p = jax.nn.softmax(warp_logits(lg, temperature, top_k), axis=-1)
            g, a, cf = tree_reject_sample(wkeys, chains, p)
        m = jnp.minimum(a + 1, max_new - n_out)
        hit = jnp.any(g[:, :, None] == stops[:, None, :], axis=-1)
        hitm = hit & (jnp.arange(depth + 1)[None, :] < m[:, None])
        any_hit = jnp.any(hitm, axis=1)
        first = jnp.argmax(hitm.astype(jnp.int32), axis=1)
        m = jnp.where(any_hit, first + 1, m)
        m = jnp.where(live, m, 0)
        acc = jnp.maximum(m - 1, 0)
        emit_mask = jnp.arange(depth + 1)[None, :] < m[:, None]
        emit = jnp.where(emit_mask, g, jnp.int32(pad_id))
        histcol = pos[:, None] + 1 + jnp.arange(depth + 1)[None, :]
        hist = hist.at[rows, jnp.where(emit_mask, histcol, hist.shape[1])
                       ].set(g, mode="drop")
        tok = jnp.where((m > 0)[:, None],
                        jnp.take_along_axis(g, acc[:, None], axis=1),
                        tok)
        vc = tree_relocate(cfg, vc, pos, acc, cf, fan=fan, depth=depth,
                           page_size=page_size)
        sel = jnp.where(acc > 0, cf * depth + acc, 0)
        pos = pos + m
        n_out = n_out + m
        done = done | (live & any_hit) | (n_out >= max_new)
        cache = commit_verify(cfg, vc, sel)
        return ((tok, cache, pos, n_out, done, hist,
                 wctr + live.astype(jnp.int32)), (emit, m))

    carry, (emits, ms) = jax.lax.scan(
        body, (tok, cache, pos, n_out, done, hist, wctr), None, length=chunk)
    tok, cache, pos, n_out, done, hist, wctr = carry
    return cache, tok, pos, n_out, done, hist, wctr, emits, ms


_spec_tree_chunk = functools.partial(
    jax.jit,
    static_argnames=("cfg", "chunk", "page_size", "fan", "depth", "ngram_n",
                     "pad_id", "greedy", "top_k"),
    donate_argnames=("cache",),
)(_spec_tree_chunk_body)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "chunk", "page_size", "fan", "depth",
                     "ngram_n", "pad_id", "greedy", "top_k"),
    donate_argnames=("cache",),
)
def _spec_tree_chunk_sharded(params, cfg: ModelConfig, cache, tok, pos,
                             n_out, done, hist, wctr, rids, max_new, stops,
                             key, temperature, extras, *, mesh, chunk: int,
                             page_size: int, fan: int, depth: int,
                             ngram_n: int, pad_id: int, greedy: bool,
                             top_k: int):
    """``_spec_tree_chunk_body`` under ``shard_map``."""

    def f(p, c, tk, ps_, no, dn, hs, wc, ri, mn, st, ky, t, ex):
        return _spec_tree_chunk_body(
            p, cfg, c, tk, ps_, no, dn, hs, wc, ri, mn, st, ky, t, ex,
            chunk=chunk, page_size=page_size, fan=fan, depth=depth,
            ngram_n=ngram_n, pad_id=pad_id, greedy=greedy, top_k=top_k)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(tree_pspecs(params),) + (P(),) * 13,
        out_specs=P(), check_vma=False,
    )(params, cache, tok, pos, n_out, done, hist, wctr, rids, max_new, stops,
      key, temperature, extras)
