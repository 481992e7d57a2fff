"""The reduction from a profiler trace to per-layer metrics, on a small
slice of a trace recorded on a v5e chip (qwen2-1.5b, chat traffic): two
admits (128 and 1024 prompt tokens) and one decode round."""
import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import check, work  # noqa: E402
from bench import trace as tr  # noqa: E402

PEAKS = json.loads((ROOT / "bench" / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]


@pytest.fixture(scope="module")
def ctx():
    t = tr.load_small(DATA / "trace_qwen2_chat.json.gz")
    with gzip.open(DATA / "trace_qwen2_chat.events.json.gz", "rt") as f:
        recs = json.load(f)["records"]
    conf = json.loads((ROOT / "bench/configs/qwen2-1.5b.json").read_text())
    report = NS(records=[NS(events=r["events"]) for r in recs])
    items = [NS(prompt_len=r["prompt_len"]) for r in recs]
    return NS(trace=t, report=report, items=items,
              hp=check.reference("dense_decoder").hyper(conf),
              serving=conf["serving"], peaks=PEAKS, chips=1, work=work)


def read(name, ctx):
    return cells.metric_reader(name)(ctx)


def test_programs_are_found_by_name(ctx):
    assert len(ctx.trace.modules(tr.DECODE)) == 1
    assert len(ctx.trace.modules(tr.ADMIT)) == 2


def test_decode_step_and_host_time(ctx):
    m = ctx.trace.modules(tr.DECODE)
    dev = float(m.end[0] - m.start[0])
    assert read("decode_step_ms", ctx) == pytest.approx(dev * 1e3 / 8)
    host = [e for r in ctx.report.records for e in r.events
            if e["name"] == "decode" and e["ts"] >= 5.0][0]["dur"]
    assert read("round_host_ms", ctx) == pytest.approx((host - dev) * 1e3)
    assert 0 < read("round_host_ms", ctx) < host * 1e3


def test_prefill_time_per_prompt_token(ctx):
    m = ctx.trace.modules(tr.ADMIT)
    dev = float(sum(m.end - m.start))
    assert read("prefill_ms_per_ktok", ctx) == pytest.approx(
        dev * 1e3 / 1.152)  # 128 + 1024 true prompt tokens
    flops = work.prefill_flops(ctx.hp, 128) + work.prefill_flops(ctx.hp, 1024)
    assert read("step_mfu.prefill", ctx) == pytest.approx(
        100 * flops / (dev * 197e12))


def test_kernel_rooflines_by_hand(ctx):
    ops = ctx.trace.ops_in(ctx.trace.modules(tr.DECODE)).select(
        lambda n: n.startswith("%pim_matvec"))
    assert len(ops) == 8 * 28 * 7  # chunk x layers x linears
    # every kernel call is one of the layer's seven (K, N), with the down
    # projection's K padded from 8960 to 9216 by the kernel's wrapper
    kn = {tr.op_shapes(n)[2][1] for n in ops.names}
    assert kn == {(1536, 1536), (1536, 256), (1536, 8960), (9216, 1536)}
    least = 0.0
    for n, s, t in zip(ops.names, ops.start, ops.end):
        (_, (m, k)), (_, (_, nn)) = tr.op_shapes(n)[1], tr.op_shapes(n)[2]
        k = 8960 if k == 9216 else k
        bias = len(tr.op_shapes(n)) > 4
        least += max(2 * m * k * nn / 197e12,
                     (k * nn + 4 * nn + 2 * m * k + 2 * m * nn
                      + (2 * nn if bias else 0)) / 819e9)
    got = read("pim_matvec_roofline", ctx)
    assert got == pytest.approx(100 * least / ops.seconds)
    assert 0 < got <= 100
    mm = read("pim_matmul_roofline", ctx)
    assert mm is not None and 0 < mm <= 100


def test_decode_mfu_counts_live_slots_at_their_contexts(ctx):
    v = read("step_mfu.decode", ctx)
    assert 0 < v < 100
    runs = [r for r in ctx.report.records if any(
        e["name"] == "decode" and e["ts"] >= 5.0 for e in r.events)]
    assert len(runs) >= 1


def test_idle_share_and_busy_time(ctx):
    idle = read("idle_share", ctx)
    assert 0 < idle < 100
    assert ctx.trace.busy_s() == pytest.approx(
        ctx.trace.window_s * (1 - idle / 100))


def test_missing_programs_read_nothing(ctx):
    assert read("allgather_share.decode", ctx) is None
    empty = NS(**{**vars(ctx), "trace": tr.Trace(0.0, 1.0, [
        {"modules": tr._events([]), "ops": tr._events([])}])})
    for name in ("decode_step_ms", "pim_matvec_roofline",
                 "pim_matmul_roofline", "step_mfu.decode",
                 "step_mfu.prefill", "prefill_ms_per_ktok", "round_host_ms",
                 "idle_share"):
        assert read(name, empty) is None, name


def test_breakdown_names_ops_and_labels_gaps(ctx):
    b = ctx.trace.breakdown(ctx.report)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("jit__")
    assert all(s > 0 for _, s in b["device_ops"] + b["idle_gaps"])
    labels = {g[0].rsplit(" ", 1)[0] for g in b["idle_gaps"]}
    assert labels <= {"decode round", "admit of request", "between rounds"}


def test_op_names_parse():
    n = ("%pim_matvec.95 = f32[8,8960]{1,0:T(8,128)S(1)} custom-call("
         "bf16[8,1536]{1,0:T(8,128)(2,1)S(1)} %fusion.150, s8[1536,8960]"
         "{1,0:T(8,128)(4,1)S(1)} %x, f32[1,8960]{1,0} %y), "
         'custom_call_target="tpu_custom_call"')
    assert tr.op_kind(n) == "pim_matvec"
    assert tr.op_kind("%all-gather-start.3 = (bf16[8]) x") == "all-gather-start"
    assert tr.op_shapes(n) == [("f32", (8, 8960)), ("bf16", (8, 1536)),
                               ("s8", (1536, 8960)), ("f32", (1, 8960))]


def test_union_seconds_and_gaps():
    import numpy as np

    busy, holes = tr.union_seconds(np.array([0.0, 0.5, 2.0]),
                                   np.array([1.0, 1.5, 2.5]), 0.0, 3.0,
                                   gaps=True)
    assert busy == pytest.approx(2.0)
    assert holes == [(1.5, 2.0), (2.5, 3.0)]
