"""The comparison that decides ``correct``, at a size a test run holds.

A sound run on the CPU comes out correct; each fault planted in the timed
path comes out not correct under the limit the chat cell uses; the control
(the reference at the next precision down in the program's place) comes out
not correct under the limit set for this size the same way (``TINY_LIMIT``)."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import cell as cells  # noqa: E402
from bench import check, harness  # noqa: E402

LIMIT = cells.load("qwen2-1.5b.chat").limit
# The limit at the tiny size, set between the readings the same way: the
# program reads 0 here (float32 on the CPU) and the control 0.05-0.10.
TINY_LIMIT = 0.03
SECONDS = 2.0


def tiny_cell(mix="tiny_open.json", config="tiny.json",
              limit=LIMIT) -> cells.Cell:
    conf = json.loads((DATA / config).read_text())
    m = json.loads((DATA / mix).read_text())
    spec = cells.load("qwen2-1.5b.chat")
    return cells.Cell("tiny", int(conf["serving"]["tensor_parallel"]), conf,
                      m, spec.end_to_end, [], limit)


def run(cell, seed=5):
    jax.clear_caches()
    result, out, err = harness.run_cell(cell, seed, SECONDS, False,
                                        t_start=time.monotonic(),
                                        require=False)
    assert out[0].startswith("compiles_in_window 0 "), out[0]
    return result, err


def test_sound_run_is_correct_and_prints_its_checks():
    result, err = run(tiny_cell())
    assert result["correct"] is True
    assert result["checks"]["max_gap"]["value"] <= LIMIT / 10
    assert err[-1].startswith("check max_gap ")
    assert list(result)[-1] == "checks"
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {m["name"] for m in tiny_cell().end_to_end}


@pytest.mark.parametrize("seed", [5, 11, 2**31 + 29])
def test_no_request_is_lost_arriving_at_an_idle_engine(seed):
    # The tiny mix leaves the engine idle between most arrivals: each must be
    # admitted when it arrives, none shed.
    result, _ = run(tiny_cell(), seed)
    assert result["attempted"] >= 6
    assert result["failed"] == 0


def test_closed_loop_counts_only_sent_requests():
    result, _ = run(tiny_cell("tiny_closed.json"))
    assert result["correct"] is True
    sent = 4 * (SECONDS / 0.005 + 1)
    assert 4 <= result["attempted"] < sent


def test_control_fails_the_limit():
    cell = tiny_cell(limit=TINY_LIMIT)
    limit = cell.limit
    st = harness.set_up(cell, 7, SECONDS, jax.devices())
    report, _ = harness.window(st, SECONDS, False, harness.CompileCounter())
    harness.free_engine(st)
    picks = check.sample(report.records, st.items, 7, harness.SAMPLE)
    got = [check.with_control(st.ref, st.params, st.hp, st.prompts[i],
                              report.records[i].tokens, check.fp8)
           for i in picks]
    prog = max(float(p.max()) for p, _ in got)
    ctrl = max(float(c.max()) for _, c in got)
    assert prog <= limit < ctrl


@pytest.fixture
def restore():
    yield
    jax.clear_caches()


def test_token_altered_where_produced_is_not_correct(monkeypatch, restore):
    from repro.serving import engine as eng

    orig = eng._decode_chunk

    def altered(*a, **kw):
        out = list(orig(*a, **kw))
        out[5] = (out[5] + 1) % 2048  # the emitted tokens
        return tuple(out)

    monkeypatch.setattr(eng, "_decode_chunk", altered)
    result, _ = run(tiny_cell())
    assert result["correct"] is False


def test_decode_step_that_keeps_its_state_is_not_correct(monkeypatch,
                                                         restore):
    from repro.models import blocks

    orig = blocks.attn_decode_paged

    def keeps_state(p, x, cache, *a, **kw):
        h, _ = orig(p, x, cache, *a, **kw)
        return h, cache  # the new token's K/V never written

    monkeypatch.setattr(blocks, "attn_decode_paged", keeps_state)
    result, _ = run(tiny_cell())
    assert result["correct"] is False


TP_SCRIPT = r"""
import json, sys, time
sys.path.insert(0, sys.argv[1]); sys.path.insert(0, sys.argv[2])
import test_bench_check as t
import jax, jax.numpy as jnp
if sys.argv[3] == "broken":
    def no_exchange(x, axis_name, *, axis=0, tiled=False, **kw):
        return jnp.concatenate([x] * 4, axis=axis)
    jax.lax.all_gather = no_exchange
result, _ = t.run(t.tiny_cell(config="tiny_tp4.json"))
print(json.dumps({"correct": result["correct"],
                  "gap": result["checks"]["max_gap"]["value"]}))
"""


@pytest.mark.parametrize("mode,want", [("sound", True), ("broken", False)])
def test_exchange_between_chips_left_out_is_not_correct(mode, want):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", TP_SCRIPT, str(ROOT), str(Path(__file__).parent),
         mode], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["correct"] is want, got
