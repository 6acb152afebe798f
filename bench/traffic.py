"""The one generator every traffic mix goes through.

A mix is a data file, ``bench/traffic/<mix>.json``. Its keys:

* ``kind``: ``open_loop`` (requests arrive on a schedule whether or not
  earlier ones finished) or ``calls`` (a program called back to back);
* ``rate_per_s`` (``open_loop``): the mean offered rate;
* ``arrivals`` (``open_loop``): ``"poisson"`` (the default), Poisson
  arrivals at a constant rate, or ``{"phases": [[seconds, weight],
  ...]}``, a rate that repeats through the phases in turn, each phase's
  rate in proportion to its weight (0 for an off phase) and the mean over
  the whole held at ``rate_per_s``; Poisson arrivals within each phase;
* ``ahead_s`` (``calls``): how many seconds of calls are dispatched
  ahead of the one whose result is read back;
* ``client_options``: options for the accelerator's runtime client,
  such as ``max_inflight_computations`` (JAX's default lets 32 calls
  wait on the chip, 0.26 s of AXPYDOT, too few to ride out a host stall);
* ``prompt`` and ``output``: length distributions, each
  ``{"dist": "lognormal", "median", "sigma", "min", "max"[, "multiple"]}``
  or ``{"dist": "cycle", "values": [...]}``;
* ``generator`` (optional): a file ``bench/traffic/<name>.py`` whose
  ``generate(mix, seed, seconds, vocab, max_len)`` makes the requests in
  place of :func:`generate`, for traffic the keys above cannot say.

Every seed gets the same work: the lengths are the distribution's
quantiles at ``(i + 0.5) / n`` (or the cycle's values in turn), paired
into requests the same way for every seed, and the gaps between
arrivals are the exponential quantiles, scaled so that all of an open
loop's ``rate_per_s * seconds`` requests fall due inside the window.
They are dealt in groups of ten that each span the range of sizes and
gaps, in one order for every seed: a seed draws only the token ids. The
order is part of the work: with the order drawn from the seed, 61 chat
requests read a TTFT p80 18% and tokens/s 8% apart from seed to seed,
while two runs of one seed stayed within 5% and 0.4%.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float          # offset from the window's start
    prompt: List[int]
    max_new_tokens: int


def load(name: str, directory: Path = DIR) -> dict:
    """The mix ``name``; a ``generator`` it names is resolved to its path
    beside the mix (``generator_path``)."""
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    if "generator" in mix:
        mix["generator_path"] = str(directory / mix["generator"])
    return mix


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths whose multiset depends on ``spec`` and ``n`` only."""
    if spec["dist"] == "cycle":
        vals = list(spec["values"])
        return np.asarray([vals[i % len(vals)] for i in range(n)], np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    out = []
    mult = int(spec.get("multiple", 1))
    for i in range(n):
        v = spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf((i + .5) / n))
        v = int(math.ceil(v / mult) * mult)
        out.append(min(max(v, spec["min"]), spec["max"]))
    return np.asarray(out, np.int64)


def count(mix: dict, seconds: float) -> int:
    """How many requests a run of ``seconds`` sends: an open loop's all
    fall due inside the window."""
    if mix["kind"] == "open_loop":
        return max(1, round(float(mix["rate_per_s"]) * seconds))
    raise ValueError(f"traffic kind {mix['kind']!r} carries no requests")


#: requests of an open loop whose lengths and gaps are shuffled among
#: themselves: each such group spans the whole range of sizes, so every
#: stretch of the window carries the same load
GROUP = 10
#: the stream that orders every seed's requests
ORDER = 0


def _shuffled(groups, rng) -> np.ndarray:
    return np.concatenate([rng.permutation(g) for g in groups])


def _spread_groups(n: int) -> list:
    """Indices ``0..n-1`` of items sorted by size, dealt into groups of
    about ``GROUP`` that each take every ``n // GROUP``-th item."""
    nb = -(-n // GROUP)
    return [np.arange(b, n, nb) for b in range(nb)]


def warp(due: np.ndarray, phases, seconds: float) -> np.ndarray:
    """Times in a window of ``seconds`` for arrivals at ``due`` under a
    constant rate, moved so that the rate follows ``phases`` (``[[seconds,
    weight], ...]``, repeated; mean rate unchanged over whole periods)."""
    period = sum(d for d, _ in phases)
    mean = sum(d * w for d, w in phases) / period
    if period <= 0 or mean <= 0:
        raise ValueError(f"phases {phases!r} carry no arrivals")
    ts, ls = [0.0], [0.0]            # phase edges, and the load up to each
    while ts[-1] < seconds:
        for d, w in phases:
            ts.append(ts[-1] + d)
            ls.append(ls[-1] + d * w / mean)
    ls = np.asarray(ls)
    u = due * np.interp(seconds, ts, ls) / seconds
    out = []
    for x in u:
        j = int(np.searchsorted(ls, x, side="right")) - 1
        out.append(ts[j] + (x - ls[j]) / (ls[j + 1] - ls[j])
                   * (ts[j + 1] - ts[j]))
    return np.asarray(out)


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             max_len: int) -> List[Request]:
    """The requests of one run of ``mix`` measuring ``seconds``, from
    ``seed``, in arrival order. Outputs are clipped so that prompt plus
    output stays below ``max_len``."""
    if "generator_path" in mix:
        from bench import harness
        own = harness.load_module(Path(mix["generator_path"]))
        return own.generate(mix, seed, seconds, vocab, max_len)
    n = count(mix, seconds)
    prompts = quantile_lengths(mix["prompt"], n)
    outputs = quantile_lengths(mix["output"], n)
    if mix["output"]["dist"] != "cycle":
        # quantiles come sorted: pair prompts and outputs by a shuffle
        # that is the same for every seed
        outputs = _rng(ORDER, 1).permutation(outputs)
    rng = _rng(ORDER, 0)
    by_size = np.lexsort((outputs, prompts))
    order = by_size[_shuffled(_spread_groups(n), rng)]
    # exponential gaps as quantiles, dealt and shuffled the same way,
    # scaled so that the last request falls due inside the window
    gaps = np.asarray([-math.log(1.0 - (i + .5) / n) for i in range(n)])
    gaps = gaps[_shuffled(_spread_groups(n), _rng(ORDER, 2))]
    gaps *= seconds / gaps.sum()
    due = np.cumsum(gaps) - gaps[0]
    arrivals = mix.get("arrivals", "poisson")
    if arrivals != "poisson":
        due = warp(due, arrivals["phases"], seconds)
    prompts, outputs = prompts[order], outputs[order]
    ids = _rng(seed, 3)
    reqs = []
    for d, p, o in zip(due, prompts, outputs):
        p = int(min(p, max_len - 2))
        o = int(max(1, min(o, max_len - 1 - p)))
        reqs.append(Request(float(d), ids.integers(1, vocab, size=p).tolist(),
                            o))
    return reqs
