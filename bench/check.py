"""The comparison that decides ``correct``.

A sample of served requests, drawn from the seed before the window, has
its prompt and served tokens run once through the configuration's plain
reference.  Two numbers are compared:

* ``gap``: the widest margin by which a served token's reference logit
  lies below the reference's best at that position (0 where the served
  token is the reference's own greedy choice);
* ``logit_err``: the largest relative L2 distance between the logits the
  engine's timed step produced at a sampled position and the reference's;
* ``argmax_mismatch``: how many of those logits rows do not put first the
  token the engine served from them (exact, with no reference: it catches
  a token altered between the step and the stream, which the two above
  follow along the served sequence).

With a ``control`` mode the reference is also computed at that lower
precision in the program's place: its ``gap`` is read at every position of
the same prompts and tokens, of the token the lower precision puts first,
and its ``logit_err`` at the same positions as the program's.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Sample", "readings", "judge"]

_ROWS = 256


@dataclasses.dataclass
class Sample:
    prompt: Sequence[int]
    served: Sequence[int]
    # logits rows ``(vocab,)`` the engine produced, and the index in
    # ``served`` of the token each row chose
    rows: List[jax.Array] = dataclasses.field(default_factory=list)
    row_index: List[int] = dataclasses.field(default_factory=list)


@functools.partial(jax.jit, static_argnames=("ref", "arch", "mode"))
def _stats(params, tokens, ref, arch, mode):
    """Per position ``p`` of ``tokens (L,)``: the reference's best logit,
    its logit of ``tokens[p + 1]``, and, under a control ``mode``, its
    logit of the token that ``mode`` puts first.  Also the hidden rows of
    both."""
    h_ref = ref.hidden(params, tokens, arch, "highest")
    h_alt = None if mode is None else ref.hidden(params, tokens, arch, mode)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    best, tgt, alt = [], [], []
    for r0 in range(0, tokens.shape[0], _ROWS):
        lr = ref.logits(params, h_ref[r0:r0 + _ROWS], arch, "highest")
        best.append(jnp.max(lr, axis=-1))
        tgt.append(jnp.take_along_axis(lr, nxt[r0:r0 + _ROWS, None],
                                       axis=-1)[:, 0])
        if mode is not None:
            la = ref.logits(params, h_alt[r0:r0 + _ROWS], arch, mode)
            pick = jnp.argmax(la, axis=-1)
            alt.append(jnp.take_along_axis(lr, pick[:, None], axis=-1)[:, 0])
    best, tgt = jnp.concatenate(best), jnp.concatenate(tgt)
    alt = best if mode is None else jnp.concatenate(alt)
    return best, tgt, alt, h_ref, h_alt


@functools.partial(jax.jit, static_argnames=("ref", "arch", "mode"))
def _row_err(params, h_ref, h_alt, pos, rows, ref, arch, mode):
    """Relative L2 distance of ``rows`` and of the ``mode`` logits from the
    reference logits at positions ``pos``."""
    lr = ref.logits(params, h_ref[pos], arch, "highest")
    norm = jnp.linalg.norm(lr, axis=-1)
    prog = jnp.linalg.norm(rows.astype(jnp.float32) - lr, axis=-1) / norm
    if mode is None:
        return prog, jnp.zeros_like(prog)
    la = ref.logits(params, h_alt[pos], arch, mode)
    return prog, jnp.linalg.norm(la - lr, axis=-1) / norm


def readings(params, samples: Sequence[Sample], ref, arch, pad_to: int,
             row_cap: int, control: Optional[str] = None) -> Dict[str, float]:
    """The compared numbers over ``samples``; with ``control`` also the
    control's, under ``control.``-prefixed names.  Every sample runs at
    one padded length, and its logits rows at one padded count, so each
    program here compiles once."""
    gap, err, c_gap, c_err = 0.0, 0.0, 0.0, 0.0
    mismatch = 0
    for s in samples:
        if not s.served:
            continue                    # judged apart: nothing was served
        seq = list(s.prompt) + list(s.served)
        n = len(seq) - 1                 # positions that have a next token
        if len(seq) > pad_to:
            raise ValueError(f"sequence of {len(seq)} exceeds {pad_to}")
        toks = np.zeros((pad_to,), np.int32)
        toks[:len(seq)] = seq
        best, tgt, alt, h_ref, h_alt = _stats(params, jnp.asarray(toks),
                                              ref, arch, control)
        first = len(s.prompt) - 1
        best, tgt, alt = (np.asarray(x, np.float64) for x in (best, tgt, alt))
        gap = max(gap, float(np.max(best[first:n] - tgt[first:n])))
        c_gap = max(c_gap, float(np.max(best[:n] - alt[:n])))
        r = len(s.row_index)
        if r:
            if r > row_cap:
                raise ValueError(f"{r} logits rows exceed {row_cap}")
            pos = np.zeros((row_cap,), np.int32)
            pos[:r] = [first + j for j in s.row_index]
            rows = jnp.stack(list(s.rows))
            rows = jnp.pad(rows, ((0, row_cap - r), (0, 0)))
            picked = np.asarray(jnp.argmax(rows, axis=-1))[:r]
            mismatch += int(np.sum(picked != np.asarray(
                [s.served[j] for j in s.row_index])))
            prog, alt_e = _row_err(params, h_ref, h_alt, jnp.asarray(pos),
                                   rows, ref, arch, control)
            err = max(err, float(jnp.max(prog[:r])))
            c_err = max(c_err, float(jnp.max(alt_e[:r])))
    out = {"gap": gap, "logit_err": err, "argmax_mismatch": mismatch}
    if control:
        out.update({"control.gap": c_gap, "control.logit_err": c_err})
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every compared number within its limit, and none missing."""
    return all(k in numbers and np.isfinite(numbers[k])
               and numbers[k] <= lim for k, lim in limits.items())


def report(numbers: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    return [f"{k}: {numbers.get(k, float('nan'))!r} (limit {lim!r})"
            for k, lim in limits.items()]
