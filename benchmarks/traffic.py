"""The one general traffic generator: reads a mix's parameters, makes requests.

A mix is a data file (``traffic/<mix>.json``). Its lengths follow a stated
distribution (``prompt_tokens``, ``output_tokens``: uniform between ``min``
and ``max``, or ``lognormal`` with the ``median`` and ``sigma`` of a public
trace, times the mix's ``length_scale``, cut to ``min``/``max``). The
generator takes ``set_size`` lengths of each kind at the middles of equal
slices of that distribution (no randomness: the set is the distribution,
coarsened), and ``--seed`` draws everything else: which prompt length meets
which output length, the order the pairs are sent in, lap after lap, and
every token id. So every seed offers the same amount of prefill and decode
work per lap, in another order and another pairing; a run on an unseen seed
follows a schedule no earlier run has followed.

``shared_prefix_tokens`` > 0 puts the same seeded tokens at the head of every
prompt. ``sessions`` (multi-turn histories) is not implemented and is an
error. What paces the requests (``loop``, ``clients``, ``rate``, ``burst``)
belongs to the loop driver the mix names, ``loops/<loop>.py``.
"""

import statistics
import threading
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np


class TrafficError(ValueError):
    """The mix asks for something the generator does not implement."""


@dataclass(frozen=True)
class Request:
    index: int             # position in this run's order
    prompt: np.ndarray     # [1, L] int32
    max_tokens: int


def _quantile(lengths: dict, p: float, scale: float) -> int:
    """The length at quantile ``p`` of a mix's stated distribution."""
    lo, hi = int(lengths["min"]), int(lengths["max"])
    kind = lengths.get("distribution", "uniform")
    if kind == "uniform":
        value = lo + p * (hi + 1 - lo)
    elif kind == "lognormal":
        z = statistics.NormalDist().inv_cdf(p)
        value = scale * float(lengths["median"]) * np.exp(
            float(lengths["sigma"]) * z)
    else:
        raise TrafficError(f"length distribution {kind!r} is not implemented")
    return int(min(max(int(value), lo), hi))


def validate(mix: dict) -> None:
    if mix.get("sessions") not in (None, 0):
        raise TrafficError("sessions (multi-turn) are not implemented yet")
    if mix.get("sampling", "greedy") != "greedy":
        raise TrafficError("only greedy sampling is implemented (the output "
                           "check is valid for greedy tokens only)")
    if mix.get("token_ids", "uniform_vocab") != "uniform_vocab":
        raise TrafficError("only token_ids 'uniform_vocab' is implemented")
    for key in ("prompt_tokens", "output_tokens"):
        lo, hi = int(mix[key]["min"]), int(mix[key]["max"])
        if not 1 <= lo <= hi:
            raise TrafficError(f"{key}: need 1 <= min <= max, got {lo}, {hi}")
        _quantile(mix[key], 0.5, float(mix.get("length_scale", 1.0)))
    if int(mix["set_size"]) < 1:
        raise TrafficError("set_size must be at least 1")
    if int(mix.get("shared_prefix_tokens") or 0) < 0:
        raise TrafficError("shared_prefix_tokens cannot be negative")


def length_set(mix: dict) -> Tuple[List[int], List[int]]:
    """The mix's prompt lengths and output lengths: ``set_size`` of each, at
    the middles of equal slices of their distributions. The same for every
    seed; the seed pairs and orders them."""
    n = int(mix["set_size"])
    scale = float(mix.get("length_scale", 1.0))
    middles = [(i + 0.5) / n for i in range(n)]
    return ([_quantile(mix["prompt_tokens"], p, scale) for p in middles],
            [_quantile(mix["output_tokens"], p, scale) for p in middles])


def longest_request(mix: dict) -> int:
    """Prompt and output tokens of the longest request a seed can pair."""
    prompts, outputs = length_set(mix)
    return max(prompts) + max(outputs)


class RequestSource:
    """Hands out this run's requests in order, to any number of clients.

    One cursor shared by all clients. Request ``i`` of a run is a function of
    the seed and ``i`` alone, whichever client sends it and whenever it is
    made: lap ``i // set_size`` pairs the prompt lengths with the output
    lengths and orders them by two permutations drawn from the seed and the
    lap's number.
    """

    def __init__(self, mix: dict, vocab_size: int, seed: int,
                 stream: str = "window"):
        validate(mix)
        self._prompts, self._outputs = length_set(mix)
        self._vocab = int(vocab_size)
        self.seed = int(seed)
        # ``stream`` keeps warm-up requests apart from the window's.
        self._entropy = [int(seed), sum(stream.encode())]
        shared = int(mix.get("shared_prefix_tokens") or 0)
        self._prefix = np.random.default_rng(self._entropy + [2]).integers(
            0, self._vocab, shared, dtype=np.int32)
        self._lock = threading.Lock()
        self._next = 0
        self._laps: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

    def _lap(self, lap: int) -> Tuple[np.ndarray, np.ndarray]:
        orders = self._laps.get(lap)
        if orders is None:
            rng = np.random.default_rng(self._entropy + [0, lap])
            n = len(self._prompts)
            orders = self._laps[lap] = (rng.permutation(n), rng.permutation(n))
        return orders

    def make(self, index: int) -> Request:
        lap, at = divmod(index, len(self._prompts))
        prompt_order, output_order = self._lap(lap)
        prompt_len = self._prompts[int(prompt_order[at])]
        out_len = self._outputs[int(output_order[at])]
        rng = np.random.default_rng(self._entropy + [1, index])
        prompt = rng.integers(0, self._vocab, (1, prompt_len), dtype=np.int32)
        shared = min(len(self._prefix), prompt_len)
        prompt[0, :shared] = self._prefix[:shared]
        return Request(index, prompt, out_len)

    def take(self) -> Request:
        with self._lock:
            index = self._next
            self._next += 1
            return self.make(index)
