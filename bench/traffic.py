"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<name>.json`` and makes its inputs from the seed.

A mix is a decode tier under elastic events:

- ``requests`` requests decode in lockstep, each over a ``prompt``-token
  prompt, in responses of ``gen`` tokens.  Every response starts again at
  position ``prompt`` with a fresh first token drawn from the seed, so the
  cache length ``prompt + gen + 1`` is never exceeded.  Requests carry the
  fixed keys ``1000 + i``, so every seed routes the same requests to the
  same ``buckets`` buckets, and every seed has the same sizes and events.
- ``nodes`` lists the node counts: the first is where serving starts, and
  events go to the others in turn and back (``[2, 4]``: 2 -> 4 -> 2 ...).
  One event is due every ``event_period_s`` seconds of window time from
  ``event_phase_s`` on, open loop; it fires at the next step boundary.
  ``event_period_s`` null means no event.
- ``cap`` rows per node shard, ``tau`` the balance slack of the plan,
  ``sample_responses`` finished responses checked against the reference.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

import numpy as np

DIR = Path(__file__).resolve().parent / "traffic"
KEY_BASE = 1000          # request i is keyed 1000 + i
MAX_RESPONSES = 4096     # responses per request a run may start


@dataclass(frozen=True)
class Mix:
    requests: int
    prompt: int
    gen: int
    buckets: int
    cap: int
    tau: float
    nodes: List[int]
    event_period_s: Optional[float]
    event_phase_s: Optional[float]
    sample_responses: int
    why: str = ""

    @classmethod
    def load(cls, name: str) -> "Mix":
        with open(DIR / f"{name}.json") as f:
            return cls(**json.load(f))

    @property
    def cache_len(self) -> int:
        return self.prompt + self.gen + 1

    def request_keys(self) -> np.ndarray:
        return np.arange(self.requests) + KEY_BASE

    def event_targets(self) -> List[int]:
        """Node counts of one whole cycle of events, back to the start."""
        return list(self.nodes[1:]) + [self.nodes[0]] if len(
            self.nodes) > 1 else []

    def events(self, seconds: float) -> List[tuple]:
        """(due second, target node count) of each event in a window."""
        if not self.event_period_s or not self.event_targets():
            return []
        cycle = self.event_targets()
        out, k = [], 0
        while self.event_phase_s + k * self.event_period_s < seconds:
            out.append((self.event_phase_s + k * self.event_period_s,
                        cycle[k % len(cycle)]))
            k += 1
        return out


@dataclass
class Inputs:
    prompts: np.ndarray   # [B, prompt] int32
    starts: np.ndarray    # [MAX_RESPONSES, B] int32: first token of each
    sample_rng: np.random.Generator   # draws the responses checked


def make_inputs(mix: Mix, vocab: int, seed: int) -> Inputs:
    """Prompt and first tokens from the seed: the same seed gives the same
    inputs; another seed gives others of the same sizes."""
    root = np.random.SeedSequence(seed % 2 ** 64)
    data, sample = root.spawn(2)
    rng = np.random.default_rng(data)
    prompts = rng.integers(0, vocab, (mix.requests, mix.prompt),
                           dtype=np.int32)
    starts = rng.integers(0, vocab, (MAX_RESPONSES, mix.requests),
                          dtype=np.int32)
    return Inputs(prompts, starts, np.random.default_rng(sample))


def weight_key_seed(seed: int) -> int:
    """The 32-bit seed of the weights' key, from the run's seed."""
    return int(np.random.SeedSequence(seed % 2 ** 64).generate_state(1)[0])
