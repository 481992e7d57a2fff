"""The readers of the program's own spans and scopes (``bench/spans.py``),
on a small slice recorded on a v5e chip by ``bench/record_slice.py``
(qwen2-1.5b at full width): one round that admits two requests (256 and
1024 prompt tokens) and two decode rounds, with the engine's host spans on
the profiler's host plane and each device op's scope from the programs'
HLO in the trace."""
import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import check, spans, work  # noqa: E402
from bench import trace as tr  # noqa: E402

PEAKS = json.loads((ROOT / "bench" / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]
NEW = ("round_schedule_ms", "round_dispatch_ms", "round_emit_ms",
       "decode_attention_ms", "decode_linears_ms")


def _ctx(d, scoped, report_spans=True):
    conf = json.loads((ROOT / "bench/configs/qwen2-1.5b.json").read_text())
    report = NS(records=[NS(events=r["events"]) for r in d["records"]])
    if report_spans:
        report.spans = d["spans"]
    return NS(trace=tr.Trace.from_json(d["trace"]), report=report,
              items=[NS(prompt_len=r["prompt_len"]) for r in d["records"]],
              hp=check.reference("dense_decoder").hyper(conf),
              serving=conf["serving"], peaks=PEAKS, chips=1, work=work,
              scoped=scoped)


@pytest.fixture(scope="module")
def data():
    with gzip.open(DATA / "trace_qwen2_spans.json.gz", "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ctx(data):
    return _ctx(data, spans.Scoped.from_json(data["scoped"]))


def read(name, ctx):
    return cells.metric_reader(name)(ctx)


def test_slice_holds_two_admits_and_two_decode_rounds(ctx):
    assert len(ctx.trace.modules(tr.ADMIT)) == 2
    assert len(ctx.trace.modules(tr.DECODE)) == 2
    assert sorted(spans.decode_rounds(ctx)) == [0, 1]
    assert len(ctx.scoped.scopes[0]) == len(ctx.trace.chips[0]["ops"])


@pytest.mark.parametrize("phase", ["schedule", "dispatch", "emit"])
def test_round_phases_are_the_mean_engine_spans(ctx, data, phase):
    per_round = {}
    for s in data["spans"]:
        per_round.setdefault(s["round"], []).append(s)
    want = []
    for ss in per_round.values():
        own = sum(s["dur"] for s in ss if s["name"] == f"engine.{phase}")
        if phase == "schedule":
            own -= sum(s["dur"] for s in ss if s["name"] == "engine.admit")
        want.append(own)
    assert read(f"round_{phase}_ms", ctx) == pytest.approx(
        1e3 * np.mean(want))
    assert read(f"round_{phase}_ms", ctx) > 0


@pytest.mark.parametrize("name, scopes", [
    ("decode_attention_ms", {"attention"}),
    ("decode_linears_ms", {"linear", "lm_head"})])
def test_decode_scopes_sum_their_ops(ctx, name, scopes):
    ops = ctx.trace.chips[0]["ops"]
    total = 0.0
    for m0, m1 in zip(*(ctx.trace.modules(tr.DECODE).start,
                        ctx.trace.modules(tr.DECODE).end)):
        for n, s, e, sc in zip(ops.names, ops.start, ops.end,
                               ctx.scoped.scopes[0]):
            if (m0 <= s and e <= m1 and sc in scopes
                    and tr.op_kind(n) not in tr.CONTAINERS):
                total += e - s
    steps = 2 * int(ctx.serving["chunk"])
    assert read(name, ctx) == pytest.approx(1e3 * total / steps)
    assert read(name, ctx) > 0


def test_layers_fit_in_the_decode_step(ctx):
    assert (read("decode_attention_ms", ctx) + read("decode_linears_ms", ctx)
            <= read("decode_step_ms", ctx))


@pytest.mark.parametrize("programs", [tr.ADMIT, tr.DECODE],
                         ids=["admit", "decode"])
def test_scope_table_rows_sum_to_the_programs_device_time(ctx, programs):
    table = spans.scope_table(ctx, programs)
    mods = ctx.trace.modules(programs)
    assert table["runs"] == len(mods)
    got = sum(table["ms_per_run"].values()) * table["runs"] / 1e3
    assert got == pytest.approx(mods.seconds, rel=1e-3)
    assert spans.NONE in table["ms_per_run"]


def test_every_idle_gap_gets_an_engine_label(ctx):
    gaps = spans.idle_gaps(ctx)
    assert gaps and all(g[0].startswith("engine.") for g in gaps), gaps
    assert all(g[1] > 0 for g in gaps)


def test_tie_offsets_match_every_engine_span(ctx, data):
    off = spans.tie_offsets(ctx)
    assert len(off) == len(data["scoped"]["host"]) == len(data["spans"])
    assert np.max(np.abs(off)) < 5e-3


def test_a_program_without_spans_or_scopes_reads_nothing(data):
    bare = spans.Scoped([], [[spans.NONE] * len(s)
                             for s in data["scoped"]["scopes"]])
    ctx = _ctx(data, bare, report_spans=False)
    for name in NEW:
        assert read(name, ctx) is None, name
    ctx = _ctx(data, None, report_spans=False)
    for name in NEW:
        assert read(name, ctx) is None, name
    assert all(not g[0].startswith("engine.") for g in spans.idle_gaps(ctx))


def test_scope_of_takes_the_innermost_named_scope():
    stack = ("jit(_decode_chunk_body)/while/body/closed_call/lm_head/linear/"
             "jit(pim_matvec)/pallas_call")
    assert spans.scope_of(stack) == "linear"
    assert spans.scope_of("jit(f)/while/body/attention/"
                          "bqhgd,bhkd->bhgqk/dot_general") == "attention"
    assert spans.scope_of("jit(f)/while/body/squeeze") == spans.NONE
    assert spans.scope_of("") == spans.NONE


def test_hlo_scopes_are_read_from_the_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("attention"):
            y = jnp.sin(x) @ x
        with jax.named_scope("linear"):
            y = jnp.tanh(y) @ x
        return y + 1.0

    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    raw = max(tmp_path.rglob("*.xplane.pb")).read_bytes()
    hlo = spans.hlo_scopes(raw)
    (name,) = [k for k in hlo if k.startswith("jit_f(")]
    assert {"attention", "linear", spans.NONE} <= set(hlo[name].values())


def test_a_gap_before_the_first_host_span_takes_the_engine_clock_span(ctx):
    """A span that opened before the profiler started is on the engine
    clock only; the gap under it still gets its name."""
    first = min(h[1] for h in ctx.scoped.host)
    late = spans.Scoped([h for h in ctx.scoped.host if h[1] > first],
                        ctx.scoped.scopes)
    gaps = spans.idle_gaps(NS(**{**vars(ctx), "scoped": late}))
    assert gaps and all(g[0].startswith("engine.") for g in gaps), gaps
