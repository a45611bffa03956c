"""How ``correct`` is decided: served tokens against the plain reference.

After the window a sample of the responses it finished, drawn from the
seed, is run through the reference of the configuration
(``bench/reference/<name>.py``) over prompt, first token and served
tokens.  At each served position the gap is the reference's best logit
less the reference's logit of the token the program served.  The widest
gap and the mean gap over the sample are compared with the limits in the
configuration file's ``check``.  For the control, the token read is the
one that the reference in a lower precision puts first.

The sample takes, for each elastic event, one response that was being
decoded across it by a request the event moved, so that the state an
event moved is checked; the rest is drawn from all finished responses.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

BATCH = 4
# the controls: the reference a precision below the configurations'
# bfloat16, with its weights rounded to int8 ("w8"), and with every matmul
# input rounded to int8 as well ("w8a8")
CONTROLS = ("w8", "w8a8")


def load_reference(name: str):
    """The plain reference ``bench/reference/<name>.py``."""
    return importlib.import_module(f"bench.reference.{name}")


@dataclass
class Response:
    index: int            # response number (0, 1, ...) in the window
    first_step: int       # window step of its first token
    tokens: np.ndarray    # [B, gen] served tokens


def pick(responses: Sequence[Response], events: Sequence[dict],
         rng: np.random.Generator, n: int) -> List[Tuple[int, int]]:
    """(response position in ``responses``, request) pairs to check."""
    chosen: List[Tuple[int, int]] = []
    for ev in events:
        spans = [i for i, r in enumerate(responses)
                 if r.first_step <= ev["step"]
                 < r.first_step + r.tokens.shape[1]]
        moved = list(ev["moved"])
        if spans and moved:
            c = (spans[int(rng.integers(len(spans)))],
                 moved[int(rng.integers(len(moved)))])
            if c not in chosen:
                chosen.append(c)
    B = responses[0].tokens.shape[0] if responses else 0
    pool = [(i, b) for i in range(len(responses)) for b in range(B)
            if (i, b) not in chosen]
    take = max(0, min(n - len(chosen), len(pool)))
    for j in rng.choice(len(pool), size=take, replace=False):
        chosen.append(pool[int(j)])
    return chosen


def sequences(responses: Sequence[Response], picks, prompts: np.ndarray,
              starts: np.ndarray):
    """Token rows [n, prompt + gen] (prompt, first token, served tokens
    but the last) and the served tokens [n, gen] they predict."""
    rows, served = [], []
    for i, b in picks:
        r = responses[i]
        rows.append(np.concatenate([prompts[b], starts[r.index, b:b + 1],
                                    r.tokens[b, :-1]]))
        served.append(r.tokens[b])
    return (np.stack(rows).astype(np.int32),
            np.stack(served).astype(np.int32))


def gaps(ref, sizes, weights, rows: np.ndarray, served: np.ndarray,
         prompt: int, controls: Sequence[str] = ()
         ) -> Dict[Optional[str], np.ndarray]:
    """Gap [n, gen] at every checked position: of the served token (key
    None), and of the token each control puts first (key: its
    precision)."""
    import jax.numpy as jnp
    out: Dict[Optional[str], List[np.ndarray]] = {None: []}
    out.update({q: [] for q in controls})
    n = len(rows)
    pad = (-n) % BATCH
    rows_p = np.concatenate([rows, np.repeat(rows[:1], pad, 0)])
    served_p = np.concatenate([served, np.repeat(served[:1], pad, 0)])
    for a in range(0, n + pad, BATCH):
        toks = jnp.asarray(rows_p[a:a + BATCH])
        lg = ref.logits(sizes, weights, toks, prompt)
        best = lg.max(-1)

        def gap_of(tok):
            got = jnp.take_along_axis(lg, tok[..., None], -1)[..., 0]
            return np.asarray(best - got)

        out[None].append(gap_of(jnp.asarray(served_p[a:a + BATCH])))
        for q in controls:
            first = ref.logits(sizes, weights, toks, prompt, q).argmax(-1)
            out[q].append(gap_of(first.astype(jnp.int32)))
        del lg, best
    return {k: np.concatenate(v)[:n] for k, v in out.items()}


NUMBERS = {"widest_gap": lambda g: float(g.max()),
           "mean_gap": lambda g: float(g.mean())}


def judge(g: Optional[np.ndarray], limits: Dict) -> Tuple[Dict, bool]:
    """Each number of ``limits`` (a name of ``NUMBERS``, its limit) beside
    its limit, and whether all are within.  No gaps, or a limit not set,
    is not correct."""
    checks, ok = {}, g is not None and g.size > 0
    for name, limit in limits.items():
        value = NUMBERS[name](g) if g is not None and g.size else None
        checks[name] = {"value": value,
                        "limit": "unset" if limit is None else limit}
        ok = ok and limit is not None and value is not None and value <= limit
    return checks, bool(ok)
