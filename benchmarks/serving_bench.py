"""Continuous-batching serving benchmark: paged scheduler vs fixed batch.

Drives a mixed-length Poisson request trace (prompt lengths and decode
budgets drawn from Poisson distributions — the arrival mix a real serving
queue sees) through both engines on a reduced config (CPU proxy; relative
numbers are what matter):

  * **fixed batch** — the scan-compiled ``ServingEngine.generate``: requests
    are grouped into batches of ``slots`` in arrival order, prompts padded
    to the global max, and every group decodes until its *longest* request
    finishes — short requests ride along, the dense cache preallocates
    ``slots * max_seq`` tokens.
  * **continuous** — ``ContinuousBatchingEngine``: finished requests retire
    at chunk boundaries and free their pages, queued requests admit into the
    freed slots, so wall-clock scales with *useful* tokens and peak cache
    memory scales with live tokens (pages in use), not ``slots * max_seq``.

Writes ``BENCH_serving.json`` (repo root): tokens/sec for both engines, the
speedup, and the cache-memory comparison (dense preallocation vs pool bytes
vs peak live page bytes).  ``--devices N`` adds the tensor-sharded axis: the
INT8 continuous engine on one device vs sharded over an N-virtual-device
``"model"`` mesh, recording tokens/sec and weight-bytes-per-device (the
quantity the mesh divides; virtual CPU devices share one socket, so
tokens/sec is a collectives-overhead proxy).  ``--speculate K`` adds the
speculation axis: the same trace through plain decode chunks vs n-gram
verify windows, under greedy decode and ``--temperature T`` sampling
(rejection-sampling verification — distribution-preserving), recording
useful tokens/sec, tokens-per-weight-stream (chunk iterations paid), and
per-slot window acceptance.  ``--fault-rate R1,R2,...`` adds the chaos
axis: the same trace under seeded fault injection (chunk faults,
stragglers, page squeezes at each rate) through the hardened
``serve_detailed`` path, recording goodput, SLO attainment, p50/p99
completion latency (virtual clock), shed/retried counts, and a
``non_shed_token_identical`` flag against the fault-free run —
``--deadline D`` additionally stamps every request with a D-virtual-
second deadline so load shedding and goodput-vs-throughput divergence
show up.  The ``prefix_router`` axis always runs: a repeated-system-prompt
Poisson trace through an uncached engine, a ``prefix_cache=True`` engine,
and a ``ReplicaRouter`` over ``--prefix-replicas`` cached replicas,
recording prefix hit rate, prefill-token savings, admission-to-first-token
(virtual seconds), and greedy+sampled token-identity to the uncached solo
baseline — plus a validated chrome-trace JSON of the router leg next to
``--out``.  Run ``python benchmarks/serving_bench.py`` (``--smoke`` for CI).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT / "src"))


def make_trace(n_requests: int, mean_prompt: int, mean_new: int,
               max_prompt: int, max_new_cap: int, vocab: int, seed: int,
               long_frac: float = 0.25, mean_new_long: int = 0):
    """Mixed-length Poisson trace: prompt lengths and decode budgets are
    Poisson draws; a ``long_frac`` fraction of requests draws its budget
    from a long-tail Poisson (``mean_new_long``) — the short/long request
    mix where fixed batching makes short requests ride along with the
    longest group member."""
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n_requests):
        mean = (mean_new_long
                if mean_new_long and rng.random() < long_frac else mean_new)
        plen = int(np.clip(rng.poisson(mean_prompt), 2, max_prompt))
        max_new = int(np.clip(rng.poisson(mean), 2, max_new_cap))
        prompt = rng.integers(0, vocab, size=plen).astype(np.int32)
        reqs.append(Request(prompt=prompt, max_new=max_new))
    return reqs


def scaled_config(cfg):
    """The scaled-up smoke config: the raw reduced config is so small that
    per-step compute is dwarfed by dispatch; ONE definition so every
    section of BENCH_serving.json measures the same model."""
    return cfg.replace(n_layers=4, d_model=256, n_heads=8, n_kv_heads=4,
                       head_dim=32, d_ff=1024)


def trace_for(kw: dict, arch: str):
    """The benchmark trace for a parsed ``kw`` dict — one construction
    shared by the main comparison, the --sharded axis, and the --speculate
    axis, so all three measure the same workload."""
    from repro.configs import get_reduced

    return make_trace(
        kw["n_requests"], kw["mean_prompt"], kw["mean_new"],
        kw["max_prompt"], kw["max_new_cap"], get_reduced(arch).vocab,
        kw["seed"], long_frac=kw["long_frac"],
        mean_new_long=kw["mean_new_long"])


def pool_geometry(slots: int, page_size: int, max_prompt: int,
                  max_new_cap: int, pool_frac: float) -> tuple[int, int]:
    """(max_seq, num_pages) — ONE formula for the main comparison and the
    --devices axis, so the two sections of BENCH_serving.json always
    benchmark the same pool."""
    max_seq = max_prompt + max_new_cap
    max_seq += -max_seq % page_size
    width = max_seq // page_size
    num_pages = max(width + 2, int(pool_frac * slots * width)) + 1
    return max_seq, num_pages


def tree_bytes(shape_tree) -> int:
    import jax

    return int(sum(np.prod(l.shape) * l.dtype.itemsize
                   for l in jax.tree.leaves(shape_tree)))


def run_fixed(engine, requests, slots: int, max_prompt: int) -> int:
    """The fixed-batch server: arrival-order groups of ``slots``, prompts
    padded to the global max prompt, decode until the group's longest
    request is done.  Returns useful (kept) tokens."""
    import jax
    import jax.numpy as jnp

    useful = 0
    for i in range(0, len(requests), slots):
        group = requests[i : i + slots]
        prompts = np.zeros((len(group), max_prompt), np.int32)
        for j, r in enumerate(group):
            prompts[j, : len(r.prompt)] = r.prompt
        n_new = max(r.max_new for r in group)
        out = engine.generate(jnp.asarray(prompts), n_new=n_new)
        jax.block_until_ready(out)
        useful += sum(min(r.max_new, n_new) for r in group)
    return useful


def run_continuous(engine, requests) -> int:
    outs = engine.serve(requests)
    return sum(len(o) for o in outs)


def bench(arch: str, n_requests: int, slots: int, page_size: int, chunk: int,
          mean_prompt: int, mean_new: int, mean_new_long: int,
          long_frac: float, max_prompt: int, max_new_cap: int,
          pool_frac: float, seed: int, scale: bool) -> dict:
    import jax
    from repro.configs import get_reduced
    from repro.models import init_cache, init_paged_cache, init_params
    from repro.serving import ContinuousBatchingEngine, ServingEngine

    cfg = get_reduced(arch)
    if scale:
        # dispatch would dwarf the raw reduced config's per-step compute,
        # flattering the zero-dispatch fixed scan; see scaled_config.
        cfg = scaled_config(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    requests = trace_for(
        dict(n_requests=n_requests, mean_prompt=mean_prompt,
             mean_new=mean_new, max_prompt=max_prompt,
             max_new_cap=max_new_cap, seed=seed, long_frac=long_frac,
             mean_new_long=mean_new_long), arch)
    max_seq, num_pages = pool_geometry(slots, page_size, max_prompt,
                                       max_new_cap, pool_frac)

    fixed = ServingEngine(cfg, params, max_seq=max_seq)
    cont = ContinuousBatchingEngine(
        cfg, params, slots=slots, max_seq=max_seq, page_size=page_size,
        num_pages=num_pages, chunk=chunk)

    # Warm (compile) both paths, then time a second identical run.
    run_fixed(fixed, requests, slots, max_prompt)
    run_continuous(cont, requests)

    t0 = time.perf_counter()
    useful_fixed = run_fixed(fixed, requests, slots, max_prompt)
    t_fixed = time.perf_counter() - t0
    t0 = time.perf_counter()
    useful_cont = run_continuous(cont, requests)
    t_cont = time.perf_counter() - t0

    # Cache memory: dense preallocation vs pool vs peak live pages.
    dense_cache = jax.eval_shape(lambda: init_cache(cfg, slots, max_seq))
    pool = jax.eval_shape(lambda: init_paged_cache(
        cfg, slots, max_seq, num_pages, page_size))
    pool1 = jax.eval_shape(lambda: init_paged_cache(
        cfg, slots, max_seq, num_pages + 1, page_size))
    page_bytes = tree_bytes(pool1) - tree_bytes(pool)  # one page, all layers
    peak_live_bytes = cont.peak_pages_in_use * page_bytes

    fixed_tps = useful_fixed / t_fixed
    cont_tps = useful_cont / t_cont
    return {
        "arch": arch,
        "trace": {
            "requests": n_requests, "slots": slots,
            "mean_prompt": mean_prompt, "mean_new": mean_new,
            "mean_new_long": mean_new_long, "long_frac": long_frac,
            "max_prompt": max_prompt, "max_new_cap": max_new_cap,
            "seed": seed,
            "prompt_lens": [len(r.prompt) for r in requests],
            "max_new": [r.max_new for r in requests],
        },
        "page_size": page_size, "chunk": chunk, "num_pages": num_pages,
        "max_seq": max_seq,
        "fixed_batch": {
            "wall_sec": t_fixed,
            "useful_tokens": useful_fixed,
            "tokens_per_sec": fixed_tps,
            "cache_bytes": tree_bytes(dense_cache),
        },
        "continuous": {
            "wall_sec": t_cont,
            "useful_tokens": useful_cont,
            "tokens_per_sec": cont_tps,
            "pool_bytes": tree_bytes(pool),
            "page_bytes": page_bytes,
            "peak_pages_in_use": cont.peak_pages_in_use,
            "peak_live_cache_bytes": peak_live_bytes,
            "preemptions": cont.preemptions,
        },
        "speedup_tokens_per_sec": cont_tps / fixed_tps,
        "peak_cache_vs_dense": peak_live_bytes / tree_bytes(dense_cache),
    }


def spec_config_for(mode: str, k: int):
    """``--spec-mode`` name -> SpecConfig.  ``fixed`` is the static
    window, ``adaptive`` the acceptance-EMA controller (collapses to
    plain decode when speculation is losing), ``tree`` a fan-2 depth-k/2
    multi-candidate draft with the same verify-node budget as ``fixed``
    (1 + fan*depth == k + 1 nodes), ``typical`` the lossy entropy-band
    acceptance on the fixed window."""
    from repro.serving import SpecConfig

    if mode == "fixed":
        return SpecConfig(k=k)
    if mode == "adaptive":
        return SpecConfig(k=k, adaptive=True)
    if mode == "tree":
        return SpecConfig(k=max(k // 2, 1), tree_fan=2)
    if mode == "typical":
        return SpecConfig(k=k, accept="typical")
    raise ValueError(f"unknown --spec-mode {mode!r}")


def bench_speculative(arch: str, requests, slots: int, page_size: int,
                      chunk: int, max_seq: int, num_pages: int,
                      speculate: int, temperature: float, scale: bool,
                      spec_modes=("fixed", "adaptive")) -> dict:
    """The speculation axis on the continuous engine: the SAME trace with
    ``speculate=0`` (plain chunks) vs each requested ``--spec-mode``
    (fixed / adaptive / tree / typical verify windows), under greedy
    decode AND ``--temperature T`` sampling (rejection-sampling
    verification), recording useful tokens/sec, ``emitted_per_stream``
    (batch-aggregate tokens per chunk iteration — each iteration streams
    the weight tree once, and it is computed for the plain row too, so the
    spec-row / 0-row ratio is the weight streams saved), and
    ``acceptance_per_live_window`` (per-slot window acceptance — the
    proposer-quality number that sampling moves).  ``typical`` is LOSSY
    and only meaningful under sampling, so its greedy leg is skipped
    (typical-with-greedy IS greedy acceptance)."""
    import jax
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.serving import ContinuousBatchingEngine

    cfg = get_reduced(arch)
    if scale:
        cfg = scaled_config(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rows = []
    modes = [(True, 0.0)]
    if temperature > 0:
        modes.append((False, temperature))
    for greedy, temp in modes:
        for mode in (None, *spec_modes):
            if mode == "typical" and greedy:
                continue
            spec = spec_config_for(mode, speculate) if mode else None
            eng = ContinuousBatchingEngine(
                cfg, params, slots=slots, max_seq=max_seq,
                page_size=page_size, num_pages=num_pages, chunk=chunk,
                speculate=spec)
            serve = lambda: sum(len(o) for o in eng.serve(
                requests, greedy=greedy, temperature=temp or 1.0,
                key=jax.random.PRNGKey(2)))
            serve()  # warm/compile
            t0 = time.perf_counter()
            useful = serve()
            dt = time.perf_counter() - t0
            # every chunk iteration streams the weights once; admit tok0s
            # come from prefill, so chunk-emitted tokens exclude one per
            # request
            chunk_emitted = useful - len(requests)
            rows.append({
                "spec_mode": mode or "plain",
                "speculate_k": spec.k if spec else 0,
                "greedy": greedy,
                "temperature": None if greedy else temp,
                "useful_tokens": useful,
                "tokens_per_sec": useful / dt,
                "emitted_per_stream": chunk_emitted
                / max(eng.decode_chunk_iters, 1),
                "acceptance_per_live_window": (eng.spec_emitted
                                               / max(eng.spec_live_steps, 1)
                                               if mode else 1.0),
            })
            if mode:
                base = [r for r in rows if r["spec_mode"] == "plain"
                        and r["greedy"] == greedy][0]
                rows[-1]["speedup_vs_plain"] = (rows[-1]["tokens_per_sec"]
                                                / base["tokens_per_sec"])
            r = rows[-1]
            tag = "greedy" if greedy else f"T={temp}"
            print(f"spec={r['spec_mode']:8s} {tag}: "
                  f"{r['tokens_per_sec']:10.1f} useful tok/s, "
                  f"{r['emitted_per_stream']:.2f} tok/stream, "
                  f"{r['acceptance_per_live_window']:.2f} tok/live-window"
                  + (f", {r.get('speedup_vs_plain', 1.0):.2f}x"
                     if mode else ""))
    return {"k": speculate, "temperature": temperature,
            "modes": list(spec_modes), "grid": rows}


def make_repetitive_trace(n_requests: int, mean_new: int, vocab: int,
                          seed: int, period: int = 4, plen: int = 24):
    """The proposer-friendly counterpart of ``make_trace``: every prompt
    is a short random pattern tiled out to ``plen``, so the trailing
    n-gram always has an earlier occurrence and the continuation is
    genuinely predictable — structured/templated generation (code, JSON,
    retrieval-echo) rather than open-ended prose."""
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n_requests):
        pat = rng.integers(0, vocab, size=period).astype(np.int32)
        prompt = np.tile(pat, plen // period + 1)[:plen]
        max_new = int(np.clip(rng.poisson(mean_new), 2, 4 * mean_new))
        reqs.append(Request(prompt=prompt, max_new=max_new))
    return reqs


def bench_repetitive(arch: str, slots: int, page_size: int, chunk: int,
                     speculate: int, seed: int, scale: bool,
                     n_requests: int = 16, mean_new: int = 48) -> dict:
    """The workload speculation exists for: repetitive/templated text
    where the n-gram proposer is near-perfect.  Plain decode vs the
    adaptive controller on the SAME repetitive trace, greedy — the
    controller must discover the high acceptance rate and hold the window
    wide (the acceptance bar: >= 1.5x plain wall-clock).  The window cap
    is ``2 * speculate``: with a measured per-extra-token window cost of
    ~ctrl_cost decode steps, the achievable speedup is roughly
    ``(a + 1) / (1 + ctrl_cost * k)``, so near-perfect acceptance wants
    DEEP windows — exactly the asymmetry the controller exploits (deep
    when winning, k=0 when losing) that a fixed k cannot."""
    import jax
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.serving import ContinuousBatchingEngine

    cfg = get_reduced(arch)
    if scale:
        cfg = scaled_config(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    requests = make_repetitive_trace(n_requests, mean_new, cfg.vocab, seed)
    max_seq, num_pages = pool_geometry(slots, page_size, 24,
                                       4 * mean_new, 1.0)
    rows = []
    for mode in (None, "adaptive"):
        spec = spec_config_for(mode, 2 * speculate) if mode else None
        eng = ContinuousBatchingEngine(
            cfg, params, slots=slots, max_seq=max_seq, page_size=page_size,
            num_pages=num_pages, chunk=chunk, speculate=spec)
        serve = lambda: sum(len(o) for o in eng.serve(requests))
        serve()  # warm/compile
        t0 = time.perf_counter()
        useful = serve()
        dt = time.perf_counter() - t0
        rows.append({
            "spec_mode": mode or "plain",
            "useful_tokens": useful,
            "tokens_per_sec": useful / dt,
            "acceptance_per_live_window": (eng.spec_emitted
                                           / max(eng.spec_live_steps, 1)
                                           if mode else 1.0),
        })
        if mode:
            rows[-1]["speedup_vs_plain"] = (rows[-1]["tokens_per_sec"]
                                            / rows[0]["tokens_per_sec"])
        r = rows[-1]
        print(f"repetitive spec={r['spec_mode']:8s}: "
              f"{r['tokens_per_sec']:10.1f} useful tok/s, "
              f"{r['acceptance_per_live_window']:.2f} tok/live-window"
              + (f", {r.get('speedup_vs_plain', 1.0):.2f}x" if mode else ""))
    return {"k": speculate, "requests": n_requests, "mean_new": mean_new,
            "grid": rows}


def bench_chaos(arch: str, requests, slots: int, page_size: int, chunk: int,
                max_seq: int, num_pages: int, fault_rates, deadline: float,
                seed: int, scale: bool) -> dict:
    """The robustness axis: the SAME trace through the hardened
    ``serve_detailed`` path at each injected fault rate (chunk faults +
    stragglers + page squeezes, all at rate R, one seeded injector per
    run).  Time runs on a virtual clock with ``round_time=1.0`` so
    deadlines, latency percentiles, and SLO attainment are DETERMINISTIC
    scheduling quantities (in virtual seconds ~ scheduling rounds), while
    goodput tokens/sec uses the wall clock.  Every row checks that all
    non-shed requests emitted exactly the fault-free run's tokens
    (``non_shed_token_identical`` — the PR-6 robustness bar, same
    assertion tests/test_chaos.py makes)."""
    import jax
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.serving import (ChaosConfig, ContinuousBatchingEngine,
                               FaultInjector, ResiliencePolicy, VirtualClock)

    cfg = get_reduced(arch)
    if scale:
        cfg = scaled_config(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    if deadline > 0:
        requests = [dataclasses.replace(r, deadline=deadline)
                    for r in requests]
    policy = ResiliencePolicy(round_time=1.0)
    key = jax.random.PRNGKey(2)
    base_outputs = None
    rows = []
    for rate in fault_rates:
        eng = ContinuousBatchingEngine(
            cfg, params, slots=slots, max_seq=max_seq, page_size=page_size,
            num_pages=num_pages, chunk=chunk, clock=VirtualClock())
        eng.serve_detailed(requests, policy=policy, key=key)  # warm/compile
        chaos = (FaultInjector(ChaosConfig(
            seed=seed, fault_rate=rate, straggle_rate=rate,
            squeeze_rate=rate)) if rate > 0 else None)
        eng2 = ContinuousBatchingEngine(
            cfg, params, slots=slots, max_seq=max_seq, page_size=page_size,
            num_pages=num_pages, chunk=chunk, clock=VirtualClock())
        t0 = time.perf_counter()
        report = eng2.serve_detailed(requests, policy=policy, chaos=chaos,
                                     key=key)
        dt = time.perf_counter() - t0
        if base_outputs is None:  # first row must be the fault-free run
            assert rate == 0
            base_outputs = [r.tokens for r in report.records]
        parity = all(
            np.array_equal(base_outputs[i], rec.tokens)
            for i, rec in enumerate(report.records) if rec.status == "done")
        lat = sorted(report.latencies())
        pct = lambda q: (float(lat[min(len(lat) - 1,
                                       int(q * (len(lat) - 1)))])
                         if lat else None)
        statuses = [r.status for r in report.records]
        rows.append({
            "fault_rate": rate,
            "goodput_tokens": report.goodput_tokens(),
            "goodput_tokens_per_sec": report.goodput_tokens() / dt,
            "slo_attainment": report.slo_attainment(),
            "p50_latency_vsec": pct(0.50),
            "p99_latency_vsec": pct(0.99),
            "done": statuses.count("done"),
            "shed": report.sheds,
            "rejected": report.rejects,
            "retried_chunks": report.retries,
            "straggle_vsec": report.straggle_s,
            "squeezed_pages": report.squeezed_pages,
            "max_ladder_level": report.max_ladder_level,
            "rounds": report.rounds,
            "non_shed_token_identical": parity,
        })
        r = rows[-1]
        print(f"fault_rate={rate}: {r['goodput_tokens_per_sec']:10.1f} "
              f"goodput tok/s, SLO {r['slo_attainment']:.2f}, "
              f"p50/p99 {r['p50_latency_vsec']}/{r['p99_latency_vsec']} "
              f"vsec, {r['retried_chunks']} retries, {r['shed']} shed, "
              f"parity={r['non_shed_token_identical']}")
    return {"fault_rates": list(fault_rates), "deadline": deadline or None,
            "round_time_vsec": 1.0, "chaos_seed": seed, "grid": rows}


def bench_sharded(arch: str, requests, slots: int, page_size: int, chunk: int,
                  max_seq: int, num_pages: int, devices: int) -> dict:
    """Continuous engine, INT8 weights, single-device vs mesh-sharded on the
    SAME trace: tokens/sec per device count + the per-device weight bytes."""
    import jax
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.serving import (ContinuousBatchingEngine, make_decode_mesh,
                               pim_bytes)

    if len(jax.devices()) < devices:
        raise RuntimeError(f"only {len(jax.devices())} devices visible; the "
                           f"--devices {devices} axis needs {devices}")
    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_decode_mesh(devices)
    rows = []
    for dc in (1, devices):
        eng = ContinuousBatchingEngine(
            cfg, params, slots=slots, max_seq=max_seq, page_size=page_size,
            num_pages=num_pages, chunk=chunk, pim_bits=8,
            mesh=None if dc == 1 else mesh)
        run_continuous(eng, requests)  # warm/compile
        t0 = time.perf_counter()
        useful = run_continuous(eng, requests)
        dt = time.perf_counter() - t0
        rows.append({
            "devices": dc,
            "useful_tokens": useful,
            "tokens_per_sec": useful / dt,
            "weight_bytes_total": pim_bytes(eng.params),
            "weight_bytes_per_device": pim_bytes(eng.params, per_device=True),
        })
        print(f"sharded devices={dc}: {rows[-1]['tokens_per_sec']:10.1f} "
              f"useful tok/s, "
              f"{rows[-1]['weight_bytes_per_device']/1e6:.3f} MB/device")
    return {"devices": devices, "grid": rows}


def make_system_prompt_trace(n_requests: int, n_system: int, sys_len: int,
                             max_tail: int, mean_new: int, max_new_cap: int,
                             vocab: int, seed: int, arrival_rate: float,
                             deadline_slack: float = 30.0):
    """The prefix-cache workload: a Poisson arrival process where every
    prompt is one of ``n_system`` repeated system prompts (page-aligned,
    ``sys_len`` tokens) plus a short random user tail — the
    few-templates/many-users mix where shared-prefix deduplication pays.
    Mixed SLOs: roughly half the requests carry a ``deadline_slack``
    deadline and a random priority class, so load shedding and SLO
    attainment stay live quantities on this axis too."""
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    sys_prompts = [rng.integers(0, vocab, size=sys_len).astype(np.int32)
                   for _ in range(n_system)]
    t, reqs = 0.0, []
    for _ in range(n_requests):
        t += float(rng.exponential(1.0 / arrival_rate))
        sp = sys_prompts[int(rng.integers(n_system))]
        tail = rng.integers(0, vocab,
                            size=int(rng.integers(0, max_tail + 1))
                            ).astype(np.int32)
        max_new = int(np.clip(rng.poisson(mean_new), 2, max_new_cap))
        deadline = (t + deadline_slack
                    if deadline_slack and rng.random() < 0.5 else None)
        reqs.append(Request(prompt=np.concatenate([sp, tail]),
                            max_new=max_new, arrival=t, deadline=deadline,
                            slo=int(rng.integers(1, 4))))
    return reqs


def bench_prefix_router(arch: str, slots: int, page_size: int, chunk: int,
                        seed: int, n_requests: int, n_system: int,
                        replicas: int, temperature: float,
                        out_path: str) -> dict:
    """The prefix-cache + fleet axis: the SAME repeated-system-prompt
    Poisson trace through (a) an uncached solo engine, (b) a
    ``prefix_cache=True`` solo engine, and (c) a ``ReplicaRouter`` over
    ``replicas`` cached engines — greedy AND sampled legs, all on a
    virtual clock with ``round_time=1.0`` and a pool sized to HALF the
    dense worst case so page pressure binds (uncached admission blocks on
    pages; cached admission aliases the shared prefix and fits).
    Records the prefix hit rate, prefill-token savings (the >=30%
    acceptance bar), mean admission-to-first-token (``t_first`` minus
    arrival, virtual seconds — deterministic), CoW/eviction counts, and
    token-identity flags of every leg against the uncached solo baseline
    (done-in-both requests).  Uses the RAW reduced config: every metric
    on this axis is a token count or virtual-time scheduling quantity,
    not wall throughput.  The router (greedy) leg's telemetry is exported
    as chrome-trace JSON next to ``out_path`` and validated before the
    bench reports it (tools/trace_export.py)."""
    import jax
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.serving import (ContinuousBatchingEngine, ReplicaRouter,
                               ResiliencePolicy, VirtualClock)

    sys.path.insert(0, str(_ROOT / "tools"))
    import trace_export

    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    sys_len, max_tail = 4 * page_size, 2 * page_size
    mean_new, max_new_cap = 6, 12
    requests = make_system_prompt_trace(
        n_requests, n_system, sys_len, max_tail, mean_new, max_new_cap,
        cfg.vocab, seed, arrival_rate=2.0)
    max_seq, num_pages = pool_geometry(slots, page_size, sys_len + max_tail,
                                       max_new_cap, 0.5)
    policy = ResiliencePolicy(round_time=1.0)
    key = jax.random.PRNGKey(2)

    def mk(prefix: bool):
        return ContinuousBatchingEngine(
            cfg, params, slots=slots, max_seq=max_seq, page_size=page_size,
            num_pages=num_pages, chunk=chunk, prefix_cache=prefix,
            clock=VirtualClock())

    def ident(base, test) -> bool:
        return all(np.array_equal(b.tokens, t.tokens)
                   for b, t in zip(base.records, test.records)
                   if b.status == "done" and t.status == "done")

    def admit_to_first(report):
        vals = [rec.t_first - req.arrival
                for rec, req in zip(report.records, requests)
                if rec.status == "done" and rec.t_first is not None]
        return float(np.mean(vals)) if vals else None

    legs = {}
    for tag, greedy in (("greedy", True), ("sampled", False)):
        kwd = dict(greedy=greedy, temperature=temperature or 0.8, top_k=20,
                   key=key, policy=policy)
        t0 = time.perf_counter()
        un = mk(False).serve_detailed(requests, **kwd)
        t_un = time.perf_counter() - t0
        t0 = time.perf_counter()
        ca = mk(True).serve_detailed(requests, **kwd)
        t_ca = time.perf_counter() - t0
        router = ReplicaRouter([mk(True) for _ in range(replicas)])
        rr = router.serve_detailed(requests, **kwd)
        legs[tag] = dict(un=un, ca=ca, rr=rr, t_un=t_un, t_ca=t_ca,
                         ident_cached=ident(un, ca),
                         ident_router=ident(un, rr))
        print(f"prefix {tag}: hits {ca.prefix_hits}/{n_requests}, prefill "
              f"{ca.prefill_tokens} vs {un.prefill_tokens} uncached tokens, "
              f"cow {ca.cow_forks}, evict {ca.evictions}, "
              f"identical cached={legs[tag]['ident_cached']} "
              f"router={legs[tag]['ident_router']}")

    g = legs["greedy"]
    un, ca, rr = g["un"], g["ca"], g["rr"]
    savings = 1.0 - ca.prefill_tokens / max(un.prefill_tokens, 1)
    a2f_un, a2f_ca = admit_to_first(un), admit_to_first(ca)
    trace_path = str(Path(out_path).with_suffix("")) + ".trace.json"
    n_events = trace_export.write_trace(
        trace_export.router_report_to_trace(rr), trace_path)
    print(f"prefix hit rate {ca.prefix_hits / n_requests:.2f}, prefill "
          f"savings {100 * savings:.0f}%, admit-to-first "
          f"{a2f_un:.2f} -> {a2f_ca:.2f} vsec, trace {trace_path} "
          f"({n_events} events)")
    return {
        "requests": n_requests,
        "system_prompts": n_system,
        "page_size": page_size,
        "num_pages": num_pages,
        "replica_count": replicas,
        "round_time_vsec": 1.0,
        "prefix_hit_rate": ca.prefix_hits / n_requests,
        "prefix_hit_tokens": ca.prefix_hit_tokens,
        "prefill_tokens_uncached": un.prefill_tokens,
        "prefill_tokens_cached": ca.prefill_tokens,
        "prefill_savings_frac": savings,
        "admit_to_first_uncached_s": a2f_un,
        "admit_to_first_cached_s": a2f_ca,
        "wall_sec_uncached": g["t_un"],
        "wall_sec_cached": g["t_ca"],
        "done_uncached": len(un.done()),
        "done_cached": len(ca.done()),
        "shed_uncached": un.sheds,
        "shed_cached": ca.sheds,
        "cow_forks": ca.cow_forks,
        "evictions": ca.evictions,
        "token_identical_greedy": bool(g["ident_cached"]
                                       and g["ident_router"]),
        "token_identical_sampled": bool(legs["sampled"]["ident_cached"]
                                        and legs["sampled"]["ident_router"]),
        "router": {
            "replicas": replicas,
            "assignments": list(map(int, rr.assignments)),
            "affinity_hits": int(rr.affinity_hits),
            "prefix_hits": rr.prefix_hits,
            "prefill_tokens": rr.prefill_tokens,
            "token_identical": bool(g["ident_router"]
                                    and legs["sampled"]["ident_router"]),
        },
        "trace_file": Path(trace_path).name,
        "trace_events": n_events,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=12)
    ap.add_argument("--mean-prompt", type=int, default=24)
    ap.add_argument("--mean-new", type=int, default=8)
    ap.add_argument("--mean-new-long", type=int, default=48)
    ap.add_argument("--long-frac", type=float, default=0.25)
    ap.add_argument("--max-prompt", type=int, default=48)
    ap.add_argument("--max-new-cap", type=int, default=64)
    ap.add_argument("--pool-frac", type=float, default=0.6,
                    help="pool size as a fraction of the dense worst case")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-scale", action="store_true",
                    help="use the raw reduced config (per-step compute "
                    "too small to be representative)")
    ap.add_argument("--devices", type=int, default=8,
                    help="width of the sharded-decode mesh axis (runs in a "
                    "subprocess with that many virtual host devices; "
                    "0/1 disables)")
    ap.add_argument("--speculate", type=int, default=4,
                    help="speculation window K for the --speculate axis "
                    "(plain vs K on the same trace; 0 disables)")
    ap.add_argument("--temperature", type=float, default=0.8,
                    help="adds a sampled leg to the --speculate axis: "
                    "rejection-sampling verification at this temperature, "
                    "recording acceptance rate and tokens-per-weight-"
                    "stream under sampling (0 disables)")
    ap.add_argument("--spec-mode", default="fixed,adaptive,tree,typical",
                    help="comma list from {fixed,adaptive,tree,typical}: "
                    "which speculation shapes the --speculate axis runs "
                    "against the plain baseline (typical is lossy and only "
                    "runs on the sampled leg)")
    ap.add_argument("--fault-rate", default="0,0.05",
                    help="comma list of injected fault rates for the chaos "
                    "axis (chunk faults + stragglers + page squeezes, "
                    "seeded); 0 is always run first as the parity/goodput "
                    "reference; empty string disables")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="stamp every request with this deadline in virtual "
                    "seconds (~scheduling rounds) on the chaos axis, so "
                    "shedding and SLO attainment bite (0 disables)")
    ap.add_argument("--prefix-replicas", type=int, default=2,
                    help="replica count for the prefix_router axis")
    ap.add_argument("--out", default=str(_ROOT / "BENCH_serving.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny trace, tiny shapes")
    ap.add_argument("--sharded-only", action="store_true",
                    help=argparse.SUPPRESS)  # subprocess entry point
    args = ap.parse_args(argv)

    if args.smoke:
        kw = dict(n_requests=6, slots=2, page_size=4, chunk=4,
                  mean_prompt=8, mean_new=6, mean_new_long=0, long_frac=0.0,
                  max_prompt=16, max_new_cap=12, pool_frac=0.75,
                  seed=args.seed, scale=False)
    else:
        kw = dict(n_requests=args.requests, slots=args.slots,
                  page_size=args.page_size, chunk=args.chunk,
                  mean_prompt=args.mean_prompt, mean_new=args.mean_new,
                  mean_new_long=args.mean_new_long, long_frac=args.long_frac,
                  max_prompt=args.max_prompt, max_new_cap=args.max_new_cap,
                  pool_frac=args.pool_frac, seed=args.seed,
                  scale=not args.no_scale)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    def sharded_axis():
        max_seq, num_pages = pool_geometry(kw["slots"], kw["page_size"],
                                           kw["max_prompt"], kw["max_new_cap"],
                                           kw["pool_frac"])
        # Same trace as the main comparison, on the raw reduced config
        # (the scaled-up config exists to drown dispatch overhead, which
        # the 1-vs-N comparison does not need).
        return bench_sharded(
            args.arch, trace_for(kw, args.arch), kw["slots"],
            kw["page_size"], kw["chunk"], max_seq, num_pages, args.devices)

    if args.sharded_only:
        print("RESULT " + json.dumps(sharded_axis()))
        return

    # The --devices axis first: a child may only start while this process
    # holds no backend (bench_subproc).
    sharded = None
    if args.devices > 1:
        from bench_subproc import run_devices_axis

        sub_args = ["--arch", args.arch, "--devices", str(args.devices),
                    "--seed", str(args.seed)] + (
                        ["--smoke"] if args.smoke else [
                            "--requests", str(args.requests),
                            "--slots", str(args.slots),
                            "--page-size", str(args.page_size),
                            "--chunk", str(args.chunk),
                            "--mean-prompt", str(args.mean_prompt),
                            "--mean-new", str(args.mean_new),
                            "--mean-new-long", str(args.mean_new_long),
                            "--long-frac", str(args.long_frac),
                            "--max-prompt", str(args.max_prompt),
                            "--max-new-cap", str(args.max_new_cap),
                            "--pool-frac", str(args.pool_frac)])
        sharded = run_devices_axis(__file__, sub_args, args.devices,
                                   sharded_axis)

    import jax

    row = bench(args.arch, **kw)
    result = {
        "bench": "serving_continuous_batching",
        "backend": jax.default_backend(),
    }
    if args.speculate > 0:
        sp_max_seq, sp_num_pages = pool_geometry(
            kw["slots"], kw["page_size"], kw["max_prompt"],
            kw["max_new_cap"], kw["pool_frac"])
        spec_requests = trace_for(kw, args.arch)
        spec_modes = tuple(m for m in args.spec_mode.split(",") if m)
        result["speculative"] = bench_speculative(
            args.arch, spec_requests, kw["slots"], kw["page_size"],
            kw["chunk"], sp_max_seq, sp_num_pages, args.speculate,
            args.temperature, kw["scale"], spec_modes=spec_modes)
        result["speculative_repetitive"] = bench_repetitive(
            args.arch, kw["slots"], kw["page_size"], kw["chunk"],
            args.speculate, kw["seed"], kw["scale"],
            n_requests=4 if args.smoke else 16,
            mean_new=12 if args.smoke else 48)
    if args.fault_rate.strip():
        rates = sorted({float(r) for r in args.fault_rate.split(",")} | {0.0})
        ch_max_seq, ch_num_pages = pool_geometry(
            kw["slots"], kw["page_size"], kw["max_prompt"],
            kw["max_new_cap"], kw["pool_frac"])
        result["chaos"] = bench_chaos(
            args.arch, trace_for(kw, args.arch), kw["slots"],
            kw["page_size"], kw["chunk"], ch_max_seq, ch_num_pages, rates,
            args.deadline, kw["seed"], kw["scale"])
    result["prefix_router"] = bench_prefix_router(
        args.arch, kw["slots"], kw["page_size"], kw["chunk"], kw["seed"],
        n_requests=12 if args.smoke else 200,
        n_system=2 if args.smoke else 6,
        replicas=args.prefix_replicas, temperature=args.temperature,
        out_path=args.out)
    result.update({
        "note": ("reduced config on CPU: tokens/sec measures scheduling "
                 "efficiency (useful tokens vs ride-along waste); "
                 "peak_live_cache_bytes is the paged pool's high-water mark "
                 "vs the dense B*max_seq preallocation; "
                 "sharded.weight_bytes_per_device is what the mesh divides; "
                 "speculative.emitted_per_stream is batch-aggregate tokens "
                 "per weight stream (chunk iteration) for BOTH rows — the "
                 "K/0 ratio is the streams saved; acceptance_per_live_window "
                 "is the per-slot proposer acceptance"),
        **row,
    })
    if sharded is not None:
        result["sharded"] = sharded
    Path(args.out).write_text(json.dumps(result, indent=2))
    fx, ct = result["fixed_batch"], result["continuous"]
    print(f"fixed batch : {fx['tokens_per_sec']:10.1f} useful tok/s "
          f"({fx['useful_tokens']} tokens, cache {fx['cache_bytes']/1e6:.2f} MB)")
    print(f"continuous  : {ct['tokens_per_sec']:10.1f} useful tok/s "
          f"({ct['useful_tokens']} tokens, peak live cache "
          f"{ct['peak_live_cache_bytes']/1e6:.2f} MB, "
          f"{ct['preemptions']} preemptions)")
    print(f"speedup {result['speedup_tokens_per_sec']:.2f}x, peak cache "
          f"{100 * result['peak_cache_vs_dense']:.0f}% of dense")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
