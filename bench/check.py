"""Whether what the timed window served is correct.

After the window, a sample of the finished requests (drawn from the seed,
always with the longest answer and the longest whole sequence in it) goes
through the configuration's plain reference, teacher-forced on the prompt and
the served tokens.  For each served token the number read is its gap: how far
its reference logit lies below the reference's best logit at that position,
over the spread (standard deviation) of that position's reference logits.
Greedy decoding at the configuration's precision serves the best token or a
near-tie, so the widest gap stays small; a computation at a lower precision
picks tokens further down.  ``correct`` holds when the widest gap is within
the cell's limit (``max_gap`` in ``bench/limits/<cell>.json``, beside the
readings it was set from).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

CONFIGS = Path(__file__).resolve().parent / "configs"


def reference(name: str):
    """The plain reference module ``bench/configs/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}", CONFIGS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(records, items, seed: int, k: int) -> list[int]:
    """Indices of ``k`` finished requests: the longest answer, the longest
    prompt plus answer, and others drawn from the seed."""
    done = [i for i, r in enumerate(records)
            if r.status == "done" and len(r.tokens) >= 1]
    if not done:
        return []
    pick = [max(done, key=lambda i: (len(records[i].tokens), -i)),
            max(done, key=lambda i: (items[i].prompt_len
                                     + len(records[i].tokens), -i))]
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 2])
    for i in rng.permutation(done):
        if len(set(pick)) >= k:
            break
        pick.append(int(i))
    return sorted(set(pick))


@jax.jit
def _gaps(lg, toks):
    best = jnp.max(lg, -1)
    got = jnp.take_along_axis(lg, toks[:, None], -1)[:, 0]
    return (best - got) / jnp.std(lg, -1)


def _rows(tokens, rows: int):
    """``tokens`` padded with zeros to the reference's row count."""
    return np.pad(np.asarray(tokens, np.int32), (0, rows - len(tokens)))


def gaps(ref, weights, hp, prompt, served) -> np.ndarray:
    """Gap of each served token (see the module docstring)."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    lg = ref.logits(weights, hp, seq, len(prompt) - 1)
    g = _gaps(lg, _rows(served, lg.shape[0]))
    return np.asarray(g)[:len(served)]


def with_control(ref, weights, hp, prompt, served, act):
    """(gaps of the served tokens, gaps of the control's tokens).  The
    control: at every position of the same prompt and served tokens, the
    token that the reference at the lower precision ``act`` puts first,
    read under the full reference."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    start = len(prompt) - 1
    low = ref.logits(weights, hp, seq, start, act=act)
    pick = jnp.argmax(low, -1).astype(jnp.int32)
    del low
    full = ref.logits(weights, hp, seq, start)
    n = len(served)
    return (np.asarray(_gaps(full, _rows(served, full.shape[0])))[:n],
            np.asarray(_gaps(full, pick))[:n])


def fp8(x):
    """The control's precision: bfloat16 activations one step down, to
    float8 (e4m3)."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
