"""Serve requests through the continuous-batching engine.

``serve`` builds a ``ContinuousBatchingEngine`` for an arch id — random
PIM-quantized weights from a seed, built layer by layer on the device (or
straight onto a decode mesh) — and serves a list of ``Request``s through
it.  The CLI below and ``chip_smoke.py`` both drive the system through it.

Usage (published width; a 1.5B model wants an accelerator):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b
CPU-sized smoke config:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \\
      --requests 4 --prompt-len 8 --new-tokens 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Sequence

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.serving import ContinuousBatchingEngine, Request, init_serving_params
from repro.serving.resilience import ServeReport


@dataclasses.dataclass
class ServeRun:
    """What one ``serve`` call built and measured (host-clock seconds,
    each span ending in a device sync)."""

    engine: ContinuousBatchingEngine
    report: ServeReport
    load_s: float   # draw + quantize + place the weights
    serve_s: float  # the serve call, compiling every program it runs
    loaded_bytes: dict  # device -> bytes_in_use after load, where reported


def make_requests(vocab: int, prompt_lens: Sequence[int],
                  max_news: Sequence[int], seed: int = 0) -> list[Request]:
    """One request per (prompt length, max_new) pair, uniform random token
    ids from ``seed``."""
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, vocab, size=int(n), dtype=np.int32),
                    max_new=int(m))
            for n, m in zip(prompt_lens, max_news)]


def device_bytes_in_use(devices) -> dict:
    """``bytes_in_use`` per device, for backends that report it."""
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        if "bytes_in_use" in stats:
            out[str(d)] = int(stats["bytes_in_use"])
    return out


def serve(arch: str, requests: Sequence[Request], *, reduced: bool = False,
          pim_bits: int = 8, slots: int = 8, seed: int = 0,
          mesh=None) -> ServeRun:
    """Build the engine for ``arch`` and greedily serve ``requests``
    through it.

    The engine's ``max_seq`` is the longest request's prompt + ``max_new``;
    ``mesh`` (``serving.make_decode_mesh``) shards the quantized weights
    over its devices as they are built."""
    cfg = get_reduced(arch) if reduced else get_config(arch)
    max_seq = max(len(r.prompt) + r.max_new for r in requests)
    t0 = time.perf_counter()
    params = init_serving_params(cfg, jax.random.PRNGKey(seed),
                                 bits=pim_bits, mesh=mesh)
    jax.block_until_ready(params)
    load_s = time.perf_counter() - t0
    devices = jax.devices()[:1] if mesh is None else list(mesh.devices.flat)
    loaded = device_bytes_in_use(devices)
    engine = ContinuousBatchingEngine(cfg, params, slots=slots,
                                      max_seq=max_seq, mesh=mesh)
    t0 = time.perf_counter()
    report = engine.serve_detailed(requests)
    serve_s = time.perf_counter() - t0
    return ServeRun(engine, report, load_s, serve_s, loaded)


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="the CPU-sized smoke config instead of the "
                    "published width")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--pim-bits", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    requests = make_requests(cfg.vocab, [args.prompt_len] * args.requests,
                             [args.new_tokens] * args.requests, args.seed)
    run = serve(args.arch, requests, reduced=args.reduced,
                pim_bits=args.pim_bits, slots=args.slots, seed=args.seed)
    total = sum(len(r.tokens) for r in run.report.records)
    print(f"{args.arch}{' (reduced)' if args.reduced else ''} on "
          f"{jax.devices()[0].device_kind}: loaded in {run.load_s:.2f}s, "
          f"served {total} tokens in {run.serve_s:.2f}s including "
          f"compilation (PIM bits={args.pim_bits})")
    print("sample:", run.report.records[0].tokens.tolist())


if __name__ == "__main__":
    main()
