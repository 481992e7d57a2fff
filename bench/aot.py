"""Compile a cell's admit and decode programs for a described v5e, without
the chip, and print what each program holds in device memory.

A rehearsal before a chip run: what the TPU compiler refuses here (a
program that does not fit, a kernel it cannot lower) costs no chip time.
It compiles the engine's own jitted steps at the cell's shapes, over one
described chip or a described ``v5e:2x2`` mesh for a tensor-parallel
configuration, and prints one JSON line per program with
``compiled.memory_analysis()``.  Nothing runs.

    JAX_PLATFORMS=cpu python bench/aot.py --workload qwen2-1.5b.chat
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import cell as cells  # noqa: E402
from bench import check, serve  # noqa: E402


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--lengths", default="",
                    help="prompt lengths to compile (default: the grid)")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.kernels import ops as kops
    from repro.models import common
    from repro.models.lm import init_paged_cache
    from repro.serving import engine as eng
    from repro.serving.sharded import decode_shardings, tree_pspecs

    cell = cells.load(args.workload)
    conf, mix = cell.config, cell.mix
    hp = check.reference(conf["reference"]).hyper(conf)
    cfg = serve.model_config(conf, hp)
    s = conf["serving"]
    tp = int(s["tensor_parallel"])
    common._KERNEL_DISPATCH = "force"
    kops._interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devs = topo.devices[:tp]
    mesh = Mesh(devs, (common.TP_AXIS,))
    rep = NamedSharding(mesh, P())

    abstract = jax.eval_shape(
        lambda k: eng.init_serving_params(cfg, k, bits=8),
        jax.random.PRNGKey(0))
    if tp > 1:
        sh = decode_shardings(abstract, mesh)

        def mark(t, shd):
            if isinstance(t, dict) and "codes" in t:
                out = dict(t)
                if common.TP_AXIS in shd["codes"].spec:
                    out["tp"] = jax.ShapeDtypeStruct(t["codes"].shape[:-2],
                                                     jnp.int8)
                return out
            if isinstance(t, dict):
                return {k: mark(v, shd[k]) for k, v in t.items()}
            return t

        abstract = mark(abstract, sh)
        specs = tree_pspecs(abstract)
        params = jax.tree.map(
            lambda a, p: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=NamedSharding(mesh, p)),
            abstract, specs)
    else:
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
            abstract)
    ps = int(s["page_size"])
    slots = int(s["slots"])
    max_seq = -(-int(mix["max_seq"]) // ps) * ps
    width = max_seq // ps
    num_pages = slots * width + 1
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(lambda: init_paged_cache(cfg, slots, max_seq,
                                                num_pages, ps)))

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    i32, f32 = jnp.int32, jnp.float32
    key = sd((2,), jnp.uint32)
    lengths = ([int(x) for x in args.lengths.split(",")] if args.lengths
               else mix["prompt"]["grid"])
    extra = {} if tp == 1 else {"mesh": mesh}
    admit = eng._admit_prefill if tp == 1 else eng._admit_prefill_sharded
    decode = eng._decode_chunk if tp == 1 else eng._decode_chunk_sharded
    for n in lengths:
        t0 = time.monotonic()
        c = admit.lower(params, cfg, cache, sd((1, n), i32), sd((), i32),
                        sd((), i32), sd((n // ps,), i32), sd((), i32), key,
                        sd((), f32), None, greedy=True, top_k=0,
                        **extra).compile()
        print(json.dumps({"program": "admit", "prompt": n,
                          "compile_s": time.monotonic() - t0,
                          "tpu_custom_call": "tpu_custom_call" in c.as_text(),
                          **_memory(c)}), flush=True)
    t0 = time.monotonic()
    c = decode.lower(params, cfg, cache, sd((slots, 1), i32), sd((slots,), i32),
                     sd((slots,), i32), sd((slots,), jnp.bool_),
                     sd((slots,), i32), sd((slots,), i32), sd((slots, 0), i32),
                     key, sd((), f32), None, chunk=int(s["chunk"]),
                     page_size=ps, greedy=True, top_k=0, pad_id=0,
                     **extra).compile()
    text = c.as_text()
    print(json.dumps({"program": "decode", "chunk": int(s["chunk"]),
                      "compile_s": time.monotonic() - t0,
                      "all_gathers": text.count("all-gather("),
                      "tpu_custom_call": "tpu_custom_call" in text,
                      **_memory(c)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
