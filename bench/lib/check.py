"""The output check that decides ``correct``.

Once the window has closed, a sample of the requests the run served,
drawn from the seed and always holding the longest one, goes through
the configuration's plain reference, teacher-forced on the prompt and
the tokens the engine served.  For every served token the check reads
how far its reference logit lies below the reference's best logit at
that position.  Serving is greedy, so a sound engine only ever picks a
token whose gap is rounding-sized.

Two numbers are read from those gaps.  The widest gap is the largest;
the mean gap is their sum over the served tokens, divided by their
count.  A lower precision flips more near-ties and by more, so the mean
gap grows about with the square of the logit error, and it is the
number compared: on the chip the widest gap of the program's int8 path
read less than three times that of sound runs.  The limit of each cell
is in ``bench/limits/<workload>.json`` with the readings it was set
from.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench.lib.model import reference_of
from bench.lib.traffic import rng_for


def load_limits(root: Path, workload: str) -> Dict[str, dict]:
    return json.loads((root / "bench" / "limits" / f"{workload}.json")
                      .read_text())


def sample(run, k: int, seed: int) -> List:
    """Up to ``k`` requests the window served: finished ones first,
    in-flight ones (with what they were served by the close) where too
    few finished; the longest is always among them.  A request of the
    lead that finished before the window opened is not the window's."""
    served = [r for r in run.records if r.tokens and not r.failed
              and r.times[-1] >= 0]
    done = [r for r in served if r.done]
    pool = done if len(done) >= k else served
    if not pool:
        return []
    longest = max(pool, key=lambda r: len(r.req.prompt) + len(r.tokens))
    rest = [r for r in pool if r is not longest]
    rng = rng_for(seed, 5)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[int(i)] for i in sorted(pick)]


def served_rows(records) -> List[tuple]:
    """(record, sequence to force, positions, served tokens) for each."""
    out = []
    for r in records:
        served = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([r.req.prompt, served[:-1]]).astype(np.int32)
        pos = len(r.req.prompt) - 1 + np.arange(len(served))
        out.append((r, seq, pos, served))
    return out


def _row_gaps(logits, target):
    """Per position: best logit minus the logit of ``target``."""
    import jax.numpy as jnp
    got = jnp.take_along_axis(logits, target[:, None], axis=1)[:, 0]
    return logits.max(axis=-1) - got


def gaps(shape, weights, rows, pad_to: int, control: bool = False
         ) -> np.ndarray:
    """For every served token of ``rows``, how far the reference's logit
    of it lies below the reference's best logit at its position.  Every
    sequence is padded to ``pad_to`` positions, so that one compiled
    program serves them all.

    With ``control``, the control takes the served tokens' place: the
    reference at int8 weights, and at each of those positions the gap
    of the token it ranks first."""
    import jax
    import jax.numpy as jnp
    ref = reference_of(shape)
    row_gaps = jax.jit(_row_gaps)
    out = []
    for _, seq, pos, served in rows:
        toks = np.zeros((pad_to,), np.int32)
        toks[:len(seq)] = seq            # causal: the tail is never seen
        target = np.zeros((pad_to,), np.int32)
        target[pos] = served
        lg = ref.logits(weights, jnp.asarray(toks), shape)
        if control:
            target = ref.logits(weights, jnp.asarray(toks), shape,
                                int8=True).argmax(axis=-1)
        out.append(np.asarray(row_gaps(lg, jnp.asarray(target)),
                              np.float64)[pos])
    return np.concatenate(out) if out else np.zeros((0,))


def readings(g: np.ndarray) -> Dict[str, float]:
    """The numbers read from the gaps: ``mean_gap`` is compared, the
    others are reported beside it."""
    if not g.size:
        return {"mean_gap": 0.0, "widest_gap": 0.0, "flip_share": 0.0}
    return {"mean_gap": float(g.mean()), "widest_gap": float(g.max()),
            "flip_share": float((g > 0).mean())}


def structural(records, vocab: int) -> List[str]:
    """Faults that need no reference: a finished request that was not
    served exactly its tokens, or a token outside the vocabulary."""
    faults = []
    for r in records:
        if r.done and len(r.tokens) != r.req.max_new:
            faults.append(f"request {r.req.index}: {len(r.tokens)} tokens "
                          f"for max_new {r.req.max_new}")
        if any(not 0 <= t < vocab for t in r.tokens):
            faults.append(f"request {r.req.index}: token out of range")
    return faults
