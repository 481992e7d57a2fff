"""Smoke run of the serving path on TPU chips, through the normal entry point.

Default (one chip): qwen2-1.5b at its published width (28 layers,
d_model 1536, vocab 151936) with random int8 weights from a seed, served
through ``repro.launch.serve.serve`` — the continuous-batching engine with
8 slots, so every decode linear dispatches to the ``pim_matvec`` kernel.
Eight requests, prompts of 128 or 512 tokens, 32 to 64 new tokens each.
Checks: every request finishes with its ``max_new`` tokens, no logits row
holds a NaN or Inf, a second serve repeats the same tokens, the compiled
decode chunk contains the kernel (``tpu_custom_call``), ``pim_matvec``
(decode) and ``pim_matmul`` (prefill, at both prompt lengths) agree with
their ``kernels.ref`` oracle at the model's shapes, int8 and int4, and
``paged_decode_attention`` agrees with the whole-table gather it replaced
at the chat cell's attention shape (8 slots, 640 pages of 8 a row).

``--chips 4``: only the tensor-sharded decode path and what it is compared
with.  starcoder2-7b at int8 on device 0, then the same model (the same
weights, checked by fingerprint) over a 4-way ``"model"`` mesh on the same
requests: greedy tokens must be identical, and each device's
``bytes_in_use`` after load shows the quantized weights split about a
quarter per device.

Everything runs in this one process (a chip belongs to one process at a
time).  The script exits non-zero, printing no result line, when JAX finds
no TPU or any check fails.  The last line of standard output is
``{"ok": true, "device": {...}}``.  The seconds printed are a cold smoke
run with compilation included, not a benchmark.

Run from the repository root:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Prompts of two lengths only, so admission compiles two prefill shapes.
PROMPT_LENS = [128, 512, 128, 512, 512, 128, 512, 128]
MAX_NEWS = [32, 64, 48, 40, 64, 32, 56, 48]

# (K, N, bias) of qwen2-1.5b's decode linears: q, k and v (with bias),
# o, gate and up, down.
QWEN2_LINEARS = [(1536, 1536, True), (1536, 256, True), (1536, 1536, False),
                 (1536, 8960, False), (8960, 1536, False)]


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"needs a TPU, but JAX found platform {d.platform!r} "
          f"({d.device_kind}, {len(devs)} device(s))")
    check(len(devs) >= chips, f"needs {chips} chips, JAX found {len(devs)}")
    return d


def served_tokens(report, requests, label: str):
    """Every request done with exactly its ``max_new`` tokens."""
    toks = []
    for i, (rec, req) in enumerate(zip(report.records, requests)):
        check(rec.status == "done", f"{label}: request {i} ended {rec.status}")
        check(len(rec.tokens) == req.max_new,
              f"{label}: request {i} emitted {len(rec.tokens)} tokens, "
              f"max_new {req.max_new}")
        toks.append([int(t) for t in rec.tokens])
    return toks


# ------------------------------------------------------------ kernel check --
# Largest error accepted over the largest |oracle| value.  Float32
# accumulation in another order leaves about 1e-6 at these shapes; rounding
# the output to bfloat16, or accumulating in it, leaves 2**-9 (2e-3) or
# more.  The per-element bound below cannot tell those apart, this can.
REL_LIMIT = 1e-5


def kernel_check(name: str, bits: int, rows: int, seed: int):
    """Max |kernel - oracle|, the max of that error over its bound, and
    the max error over the max |oracle|, over qwen2-1.5b's quantized
    linears with ``rows`` activation rows, on the chip.  ``name`` is
    ``pim_matvec`` (decode, at most 8 rows) or ``pim_matmul`` (prefill).

    Bound: activations are bfloat16 and the codes small integers, so
    every product is exact in float32 however the MXU splits it; only the
    order of the float32 accumulation differs between the kernel (blocked
    over K) and the oracle (``kernels.ref.pim_matvec_ref``, the same
    epilogue order at any row count, run at "highest" precision).  Summing
    K terms in float32 in any order is within K * 2**-24 of the exact sum,
    relative to the sum of magnitudes; the bound takes twice that, to
    cover the epilogue's scale multiply and bias add:
        |kernel - oracle| <= K * 2**-23 * (|x| @ |codes| * scale + |bias|).
    It is a worst case, loose enough to pass a bfloat16 output too, so
    ``REL_LIMIT`` is checked beside it.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.pim_matmul import pim_matmul
    from repro.kernels.pim_matvec import pim_matvec
    from repro.quant import pack_int4, quantize_symmetric

    kernel = {"pim_matvec": pim_matvec, "pim_matmul": pim_matmul}[name]
    key = jax.random.PRNGKey(seed)
    worst_err, worst_ratio, worst_rel = 0.0, 0.0, 0.0
    for k, n, has_bias in QWEN2_LINEARS:
        key, kx, kw, kb = jax.random.split(key, 4)
        x = jax.random.normal(kx, (rows, k), jnp.float32).astype(jnp.bfloat16)
        w = 0.02 * jax.random.normal(kw, (k, n), jnp.float32)
        bias = 0.02 * jax.random.normal(kb, (n,), jnp.float32) if has_bias else None
        q = quantize_symmetric(w, bits=bits, axis=0)
        codes = q.codes if bits == 8 else pack_int4(q.codes)
        got = kernel(x, codes, q.scale, bits=bits, bias=bias)
        with jax.default_matmul_precision("highest"):
            want = ref.pim_matvec_ref(x, codes, q.scale, bits=bits, bias=bias)
            mag = jnp.abs(x.astype(jnp.float32)) @ jnp.abs(
                q.codes.astype(jnp.float32)) * q.scale
        if bias is not None:
            mag = mag + jnp.abs(bias)
        bound = k * 2.0 ** -23 * mag
        err = jnp.abs(got - want)
        worst_err = max(worst_err, float(jnp.max(err)))
        worst_ratio = max(worst_ratio, float(jnp.max(err / bound)))
        worst_rel = max(worst_rel,
                        float(jnp.max(err) / jnp.max(jnp.abs(want))))
    return worst_err, worst_ratio, worst_rel


# Largest error accepted over the largest |gather| value for the paged
# attention kernel.  Both paths round the softmax probabilities to bfloat16
# once (2**-9 relative), the kernel before it normalises and the gather
# after, so they differ by about 2 * 2**-9 of the weighted sum at most
# (5e-4 to 6e-4 read in interpret mode at 48 pages a row); a page read
# twice, skipped or masked wrongly reads 1e-1 or more.
ATTN_REL_LIMIT = 2.0 ** -8


def paged_attention_check(seed: int):
    """Max |kernel - gather| over max |gather|, ``kernels.paged_attention``
    against ``models.attention.paged_gather_attention`` on the chip, at
    qwen2-1.5b's heads (12 query, 2 KV, 128 wide) in bfloat16: shuffled
    page ids, two free slots at length 1 on the trash page, the others at
    lengths drawn up to the whole row."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import paged_decode_attention
    from repro.models.attention import paged_gather_attention

    slots, width, page = 8, 640, 8
    n_heads, n_kv, d = 12, 2, 128
    pages = slots * width + 1
    rng = np.random.default_rng(seed)
    bt = rng.permutation(np.arange(1, pages)).reshape(slots, width)
    lengths = rng.integers(1, width * page + 1, slots)
    lengths[-1] = width * page
    bt[:2], lengths[:2] = 0, 1
    key = jax.random.split(jax.random.PRNGKey(seed), 3)
    k, v = (jax.random.normal(kk, (pages, n_kv, page, d), jnp.bfloat16)
            for kk in key[:2])
    q = jax.random.normal(key[2], (slots, n_heads, d), jnp.bfloat16)
    bt, lengths = jnp.asarray(bt, jnp.int32), jnp.asarray(lengths, jnp.int32)
    got = paged_decode_attention(q, k, v, bt, lengths)
    want = jax.jit(lambda *a: paged_gather_attention(
        *a, n_kv=n_kv, dtype=jnp.bfloat16))(
        q[:, None], {"k": k, "v": v}, bt, lengths - 1)
    want = want.reshape(slots, n_heads, d)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# --------------------------------------------------------------- phases -----
def phase_one_chip(seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.launch.serve import make_requests, serve

    arch = "qwen2-1.5b"
    cfg = get_config(arch)
    requests = make_requests(cfg.vocab, PROMPT_LENS, MAX_NEWS, seed)
    run = serve(arch, requests, pim_bits=8, slots=8, seed=seed)
    cold = served_tokens(run.report, requests, "cold serve")
    eng = run.engine
    check(eng.nonfinite_logits == 0,
          f"{eng.nonfinite_logits} logits rows held a NaN or Inf")
    t0 = time.perf_counter()
    warm = served_tokens(eng.serve_detailed(requests), requests, "warm serve")
    warm_s = time.perf_counter() - t0
    check(warm == cold, "a second serve of the same requests changed tokens")
    check(eng.nonfinite_logits == 0,
          f"{eng.nonfinite_logits} logits rows held a NaN or Inf")
    has_kernel = "tpu_custom_call" in eng.decode_chunk_text()
    stats = jax.devices()[0].memory_stats() or {}
    n_tok = sum(r.max_new for r in requests)
    print(f"model: {arch} full width, {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab}, int8 weights, slots 8, "
          f"{len(requests)} requests, prompts {sorted(set(PROMPT_LENS))}")
    print("cold smoke run, not a benchmark (host clock, compilation "
          "included):")
    print(f"  load_s {run.load_s:.3f}  first_serve_s {run.serve_s:.3f}  "
          f"second_serve_s {warm_s:.3f}  "
          f"compile_s~{run.serve_s - warm_s:.3f}")
    print(f"requests finished: {len(cold)}/{len(requests)}, "
          f"{n_tok} tokens, every request at its max_new")
    print(f"nonfinite logits rows: {eng.nonfinite_logits}")
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"decode chunk contains tpu_custom_call: {has_kernel}")
    check(has_kernel, "the compiled decode chunk has no tpu_custom_call: "
          "pim_matvec is not in the decode program")
    print("kernels vs kernels.ref at qwen2-1.5b shapes (bound "
          "K*2^-23*(|x|@|codes|*scale+|bias|), pass: err/bound <= 1 and "
          f"err/max|ref| <= {REL_LIMIT:g}):")
    for name, rows in [("pim_matvec", 8)] + [("pim_matmul", n)
                                             for n in sorted(set(PROMPT_LENS))]:
        for bits in (8, 4):
            err, ratio, rel = kernel_check(name, bits, rows, seed)
            print(f"  {name} int{bits} {rows} rows: max abs err {err:.3e}, "
                  f"max err/bound {ratio:.3e}, max err/max|ref| {rel:.3e}")
            check(ratio <= 1.0,
                  f"{name} int{bits} {rows} rows: error exceeds its bound")
            check(rel <= REL_LIMIT, f"{name} int{bits} {rows} rows: error "
                  f"over max|ref| exceeds {REL_LIMIT:g}")
    rel = paged_attention_check(seed)
    print(f"paged_decode_attention vs the whole-table gather, 8 slots x 640 "
          f"pages of 8, bf16: max err/max|gather| {rel:.3e} "
          f"(pass: <= {ATTN_REL_LIMIT:g})")
    check(rel <= ATTN_REL_LIMIT, "paged_decode_attention: error over "
          f"max|gather| exceeds {ATTN_REL_LIMIT:g}")


def _fingerprints(params) -> dict:
    """Per-leaf exact checksums (sums of the raw bits, plain and
    position-weighted, modulo 2**32) — equal exactly when the weights are,
    up to a collision, at any placement."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def leaf_print(x):
        width = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        u = lax.bitcast_convert_type(x, width).astype(jnp.uint32)
        idx, stride = jnp.zeros(x.shape, jnp.uint32), 1
        for d in reversed(range(x.ndim)):
            idx = idx + lax.broadcasted_iota(jnp.uint32, x.shape, d) * (
                stride % 2 ** 32)
            stride *= x.shape[d]
        return jnp.sum(u), jnp.sum(u * (2 * idx + 1))

    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        if name.endswith("['tp']"):
            continue
        out[name] = tuple(int(v) for v in leaf_print(leaf))
    return out


def phase_four_chips(seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.launch.serve import make_requests, serve
    from repro.serving import make_decode_mesh, pim_bytes

    arch = "starcoder2-7b"
    cfg = get_config(arch)
    requests = make_requests(cfg.vocab, PROMPT_LENS, [32] * len(PROMPT_LENS),
                             seed)
    one = serve(arch, requests, pim_bits=8, slots=8, seed=seed)
    toks_one = served_tokens(one.report, requests, "device 0")
    prints_one = _fingerprints(one.engine.params)
    total_w = pim_bytes(one.engine.params)
    loaded_one = one.loaded_bytes
    check(one.engine.nonfinite_logits == 0, "device 0: non-finite logits")
    one_s = (one.load_s, one.serve_s)
    del one
    gc.collect()

    mesh = make_decode_mesh(4)
    four = serve(arch, requests, pim_bits=8, slots=8, seed=seed, mesh=mesh)
    toks_four = served_tokens(four.report, requests, "4-way mesh")
    check(four.engine.nonfinite_logits == 0, "4-way mesh: non-finite logits")
    same_w = _fingerprints(four.engine.params) == prints_one
    per_dev_w = pim_bytes(four.engine.params, per_device=True)
    print(f"model: {arch} full width, {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, int8 weights {total_w} bytes, slots 8, "
          f"{len(requests)} requests of 32 new tokens")
    print("cold smoke run, not a benchmark (host clock, compilation "
          "included):")
    print(f"  device 0: load_s {one_s[0]:.3f} serve_s {one_s[1]:.3f}; "
          f"4-way mesh: load_s {four.load_s:.3f} serve_s {four.serve_s:.3f}")
    (dev0, b_one), = loaded_one.items()
    print(f"bytes_in_use after load, device 0 alone: {dev0} {b_one}")
    for dev, b in four.loaded_bytes.items():
        print(f"bytes_in_use after load, 4-way mesh: {dev} {b} "
              f"({b / b_one:.3f} of device 0 alone)")
    print(f"weight bytes per device on the mesh: {per_dev_w} "
          f"({per_dev_w / total_w:.3f} of {total_w})")
    print(f"same weights on both (per-leaf fingerprints): {same_w}")
    n_same = sum(a == b for a, b in zip(toks_one, toks_four))
    print(f"greedy tokens identical: {n_same}/{len(requests)} requests")
    if n_same < len(requests):
        first = [next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), -1)
                 for a, b in zip(toks_one, toks_four)]
        print(f"first differing token per request (-1: none): {first}")
    check(same_w, "the mesh build drew different weights")
    check(toks_one == toks_four,
          "greedy tokens differ between device 0 and the 4-way mesh")
    for b in four.loaded_bytes.values():
        check(b < 0.4 * b_one, "a mesh device holds more than 0.4 of the "
              "one-chip load: the weights are not split")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-sharded four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    try:
        dev = require_tpu(args.chips)
        print(f"compile cache: {enable_compile_cache()}")
        if args.chips == 4:
            phase_four_chips(args.seed)
        else:
            phase_one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
