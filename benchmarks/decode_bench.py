"""Decode fast-path benchmark: scan-compiled generation vs the seed loop.

Measures, on the registry's reduced configs (CPU proxy — the relative
numbers are what matter; the roofline report converts HBM-byte counts into
TPU time):

  * tokens/sec of the scan-compiled ``ServingEngine.generate`` (single-pass
    prefill + ``lax.scan`` decode, ONE XLA program) for dense / INT8 / INT4
    weight storage;
  * weight-bytes/token — the HBM bytes streamed per decode step, the
    quantity PIM storage actually improves (paper Fig 7);
  * the head-to-head vs the seed per-token Python loop
    (``generate_reference``) at batch 4, prompt 64, 32 new tokens — the
    dispatch-overhead tax the tentpole removes;
  * the ``--devices N`` axis: the INT8 engine single-device vs
    tensor-sharded over an N-virtual-device ``"model"`` mesh
    (``--xla_force_host_platform_device_count``), recording tokens/sec AND
    weight-bytes-streamed-per-device — on real hardware the per-device
    weight stream is what bounds memory-bound decode, so its 1/N drop is
    the PiCaSO scaling story (virtual CPU devices share one socket, so the
    tokens/sec column is a collectives-overhead proxy, not a speedup);
  * the ``--speculate K`` axis: plain vs speculative multi-token decode
    (n-gram proposer + one verify forward per window), under greedy decode
    AND ``--temperature T`` sampling (rejection-sampling verification),
    recording tokens/sec, emitted-tokens-per-verify-step and the
    per-window acceptance rate — each verify step streams the weights
    ONCE, so emitted/step is tokens-per-weight-stream, the multiplier on
    the weight-bytes-per-token win.

Writes ``BENCH_decode.json`` (repo root) for the PR-over-PR perf trajectory.
Run: ``python benchmarks/decode_bench.py`` (add ``--quick`` for CI smoke).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT / "src"))

ARCHS = ["qwen2-1.5b", "llama3.2-3b", "starcoder2-7b"]
BITS = [0, 8, 4]  # dense / INT8 / INT4 PIM storage


def _timed(fn, reps: int) -> float:
    import jax

    jax.block_until_ready(fn())  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps


def bench_grid(archs, batch: int, prompt_len: int, n_new: int, reps: int):
    import jax
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.serving import ServingEngine
    from repro.serving.engine import pim_bytes

    rows = []
    for arch in archs:
        cfg = get_reduced(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompt = jax.random.randint(
            jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab)
        for bits in BITS:
            eng = ServingEngine(cfg, params, max_seq=prompt_len + n_new,
                                pim_bits=bits)
            dt = _timed(lambda: eng.generate(prompt, n_new=n_new), reps)
            wbytes = pim_bytes(eng.params)
            rows.append({
                "arch": arch,
                "bits": bits,
                "batch": batch,
                "prompt": prompt_len,
                "new_tokens": n_new,
                "sec_per_call": dt,
                "tokens_per_sec": batch * n_new / dt,
                # every matmul weight is streamed once per decode step
                "weight_bytes_per_token": wbytes,
            })
            print(f"{arch:16s} bits={bits}  {rows[-1]['tokens_per_sec']:10.1f} tok/s"
                  f"  {wbytes/1e6:8.3f} MB weights/token")
    return rows


def bench_fastpath_vs_seed(arch: str, batch: int, prompt_len: int, n_new: int,
                           reps: int):
    """The acceptance comparison: scan-compiled generate vs the seed
    per-token loop, identical model and greedy decoding."""
    import jax
    import numpy as np
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.serving import ServingEngine

    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab)
    eng = ServingEngine(cfg, params, max_seq=prompt_len + n_new, pim_bits=8)

    fast = _timed(lambda: eng.generate(prompt, n_new=n_new), reps)
    seed = _timed(lambda: eng.generate_reference(prompt, n_new=n_new),
                  max(1, reps // 2))
    same = bool(np.array_equal(np.asarray(eng.generate(prompt, n_new=n_new)),
                               np.asarray(eng.generate_reference(prompt, n_new=n_new))))
    out = {
        "arch": arch,
        "batch": batch,
        "prompt": prompt_len,
        "new_tokens": n_new,
        "seed_loop_tokens_per_sec": batch * n_new / seed,
        "fastpath_tokens_per_sec": batch * n_new / fast,
        "speedup": seed / fast,
        "tokens_match_seed": same,
    }
    print(f"fastpath vs seed ({arch}, b={batch}, s={prompt_len}, n={n_new}): "
          f"{out['speedup']:.1f}x  (seed {out['seed_loop_tokens_per_sec']:.1f} -> "
          f"fast {out['fastpath_tokens_per_sec']:.1f} tok/s, "
          f"tokens match: {same})")
    return out


def bench_speculative(archs, batch: int, prompt_len: int, n_new: int,
                      reps: int, speculate: int, temperature: float):
    """The speculation axis: INT8 engine, ``--speculate K`` vs the plain
    scan (K=0), under greedy decode AND temperature sampling
    (``--temperature T``: rejection-sampling verification).  Records
    tokens/sec, the realised emitted-tokens-per-verify-step (each verify
    step streams the weight tree ONCE, so emitted/step is the
    tokens-per-weight-stream multiplier on the weight-bytes-per-token
    bound the grid section records) and the per-window acceptance rate
    (``acceptance_per_live_row`` — per-row tokens per live verify window,
    the proposer-quality number sampling moves)."""
    import jax
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.serving import ServingEngine, SpecConfig

    rows = []
    for arch in archs:
        cfg = get_reduced(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompt = jax.random.randint(
            jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab)
        eng = ServingEngine(cfg, params, max_seq=prompt_len + n_new,
                            pim_bits=8)
        modes = [(True, 0.0)]
        if temperature > 0:
            modes.append((False, temperature))
        for greedy, temp in modes:
            for k in (0, speculate):
                spec = SpecConfig(k=k) if k else None
                dt = _timed(lambda: eng.generate(
                    prompt, n_new=n_new, speculate=spec, greedy=greedy,
                    temperature=temp or 1.0,
                    key=jax.random.PRNGKey(2)), reps)
                row = {
                    "arch": arch,
                    "speculate_k": k,
                    "greedy": greedy,
                    "temperature": None if greedy else temp,
                    "tokens_per_sec": batch * n_new / dt,
                    "emitted_per_step": (eng.spec_stats["emitted_per_step"]
                                         if k else 1.0),
                    "acceptance_per_live_row": (
                        eng.spec_stats["acceptance_per_live_row"]
                        if k else 1.0),
                }
                if k:
                    base = [r for r in rows
                            if r["arch"] == arch and r["speculate_k"] == 0
                            and r["greedy"] == greedy][0]
                    row["speedup_vs_plain"] = (row["tokens_per_sec"]
                                               / base["tokens_per_sec"])
                rows.append(row)
                tag = "greedy" if greedy else f"T={temp}"
                extra = (f"  {row.get('speedup_vs_plain', 1.0):5.2f}x, "
                         f"{row['emitted_per_step']:.2f} tok/stream, "
                         f"{row['acceptance_per_live_row']:.2f} acc/window"
                         if k else "")
                print(f"{arch:16s} speculate={k} {tag:8s} "
                      f"{row['tokens_per_sec']:10.1f} tok/s{extra}")
    return rows


def bench_sharded(archs, batch: int, prompt_len: int, n_new: int, reps: int,
                  devices: int):
    """The multi-device axis: the INT8 engine on one device vs tensor-
    sharded over a ``devices``-wide 'model' mesh — tokens/sec plus the
    weight bytes ONE device holds/streams per token (total and per-device
    must differ by ~devices x for the distributed leaves)."""
    import jax
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.serving import ServingEngine, make_decode_mesh, pim_bytes

    if len(jax.devices()) < devices:
        raise RuntimeError(f"only {len(jax.devices())} devices visible; the "
                           f"--devices {devices} axis needs {devices}")
    mesh = make_decode_mesh(devices)
    rows = []
    for arch in archs:
        cfg = get_reduced(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompt = jax.random.randint(
            jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab)
        for dc in (1, devices):
            eng = ServingEngine(cfg, params, max_seq=prompt_len + n_new,
                                pim_bits=8, mesh=None if dc == 1 else mesh)
            dt = _timed(lambda: eng.generate(prompt, n_new=n_new), reps)
            rows.append({
                "arch": arch,
                "devices": dc,
                "tokens_per_sec": batch * n_new / dt,
                "weight_bytes_total": pim_bytes(eng.params),
                "weight_bytes_per_device": pim_bytes(eng.params,
                                                     per_device=True),
            })
            r = rows[-1]
            print(f"{arch:16s} devices={dc}  {r['tokens_per_sec']:10.1f} tok/s"
                  f"  {r['weight_bytes_per_device']/1e6:8.3f} MB weights/device")
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--devices", type=int, default=8,
                    help="width of the sharded-decode mesh axis (runs in a "
                    "subprocess with that many virtual host devices; "
                    "0/1 disables)")
    ap.add_argument("--speculate", type=int, default=4,
                    help="speculation window K for the --speculate axis "
                    "(K=0 plain vs K, n-gram proposer; 0 disables)")
    ap.add_argument("--temperature", type=float, default=0.8,
                    help="adds a sampled leg to the --speculate axis: "
                    "rejection-sampling verification at this temperature, "
                    "recording acceptance rate and tokens-per-weight-"
                    "stream under sampling (0 disables)")
    ap.add_argument("--out", default=str(_ROOT / "BENCH_decode.json"))
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: one arch, tiny shapes")
    ap.add_argument("--sharded-only", action="store_true",
                    help=argparse.SUPPRESS)  # subprocess entry point
    args = ap.parse_args(argv)

    if args.quick:
        archs, batch, prompt, new, reps = ARCHS[:1], 2, 8, 4, 1
    else:
        archs, batch, prompt, new, reps = (ARCHS, args.batch, args.prompt,
                                           args.new_tokens, args.reps)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    def sharded_axis():
        return bench_sharded(archs, batch, prompt, new, reps, args.devices)

    if args.sharded_only:
        print("RESULT " + json.dumps(sharded_axis()))
        return

    # The --devices axis first: a child may only start while this process
    # holds no backend (bench_subproc).
    rows = None
    if args.devices > 1:
        from bench_subproc import run_devices_axis

        sub_args = ["--devices", str(args.devices), "--batch", str(args.batch),
                    "--prompt", str(args.prompt),
                    "--new-tokens", str(args.new_tokens),
                    "--reps", str(args.reps)] + (
                        ["--quick"] if args.quick else [])
        rows = run_devices_axis(__file__, sub_args, args.devices,
                                sharded_axis)

    import jax

    result = {
        "bench": "decode_fastpath",
        "backend": jax.default_backend(),
        "note": ("reduced configs on CPU are a dispatch-overhead proxy; "
                 "weight_bytes_per_token is the HBM quantity PIM improves; "
                 "sharded.weight_bytes_per_device is what the mesh divides"),
        "grid": bench_grid(archs, batch, prompt, new, reps),
        "fastpath_vs_seed": bench_fastpath_vs_seed(
            archs[0], batch, prompt, new, reps),
    }
    if args.speculate > 0:
        result["speculative"] = {
            "k": args.speculate,
            "temperature": args.temperature,
            "grid": bench_speculative(archs, batch, prompt, new, reps,
                                      args.speculate, args.temperature),
        }
    if rows is not None:
        result["sharded"] = {"devices": args.devices, "grid": rows}
    out_path = Path(args.out)
    out_path.write_text(json.dumps(result, indent=2))
    print(f"wrote {out_path}")


# ------------------------------------------------------- run.py smoke hook --
def decode_smoke():
    """Tiny decode fast-path row set for the aggregate benchmark harness."""
    import jax
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.serving import ServingEngine

    cfg = get_reduced("qwen2-1.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    rows = []
    for bits in (0, 8):
        eng = ServingEngine(cfg, params, max_seq=16, pim_bits=bits)
        dt = _timed(lambda: eng.generate(prompt, n_new=4), 2)
        rows.append((f"decode/scan_generate_bits{bits}", dt * 1e6,
                     f"{2 * 4 / dt:.1f} tok/s"))
    eng = ServingEngine(cfg, params, max_seq=16, pim_bits=8)
    dt = _timed(lambda: eng.generate_reference(prompt, n_new=4), 1)
    rows.append(("decode/seed_token_loop_bits8", dt * 1e6,
                 f"{2 * 4 / dt:.1f} tok/s (dispatch-bound baseline)"))
    return rows


ALL = [decode_smoke]


if __name__ == "__main__":
    main()
