"""Traffic: one general generator that reads a workload's JSON.

A workload file gives `arrival` (`closed` with `clients`; `poisson` with
`rate`; `bursty` with `rate` and `cv`), the two length distributions
(`prompt_tokens`, `output_tokens`: log-normal by median and sigma, clipped),
optional prefix sharing (`prefix`: `groups`, `prefix_tokens`, `turns`) and
`pool`, the number of requests in one cycle of the mix.

Every seed sends the SAME schedule: the pool of lengths, their pairing, the
gaps and the first-request fractions are drawn once, in order, from the file's
own `pool_seed`. `--seed` draws the token ids (and, in the harness, the
weights) and nothing else, so runs with different seeds do the same work and
their spread is the system's, not the draw's. (With the order permuted by the
seed, seeds differed by 3% in tokens/s where two runs of one seed differed by
0.2%; with only the client labels permuted, which decides the order of the
first admissions, still by 2%: PERF.md, PR 24.) Pure: the same file and seed
give the same plan; nothing here touches JAX or the program.
"""

from __future__ import annotations

import dataclasses
import json
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request as the generator plans it."""

    index: int  # position in the stream (open loop) or in the client's list
    client: int
    prompt_len: int
    output_len: int
    group: int  # shared-prefix group, -1 for none
    session: int  # requests of one session extend each other's prompts
    turn: int
    due_s: float  # open loop: seconds after the stream's start; closed: 0.0


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    kind = dist.get("dist", "lognormal")
    if kind == "fixed":
        x = np.full(n, float(dist["value"]))
    elif kind == "lognormal":
        x = np.exp(rng.normal(math.log(dist["median"]), dist["sigma"], n))
    elif kind == "uniform":
        x = rng.uniform(dist["min"], dist["max"], n)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", 1 << 30)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _gaps(arrival: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Inter-arrival gaps in seconds, mean 1/rate. `bursty` is a gamma
    renewal process with the stated coefficient of variation (cv 1 is
    Poisson; cv 3 puts most arrivals into bursts)."""
    rate = float(arrival["rate"])
    if arrival["kind"] == "poisson":
        return rng.exponential(1.0 / rate, n)
    cv = float(arrival["cv"])
    shape = 1.0 / (cv * cv)
    return rng.gamma(shape, 1.0 / (rate * shape), n)


class Plan:
    """The whole run's requests, as a pure function of (workload, seed)."""

    def __init__(self, workload: dict, seed: int, vocab: int):
        w = self.workload = workload
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.arrival = w["arrival"]
        self.kind = self.arrival["kind"]
        if self.kind not in ("closed", "poisson", "bursty"):
            raise ValueError(f"unknown arrival kind {self.kind!r}")
        self.clients = int(self.arrival.get("clients", 1))
        self.pool = int(w["pool"])
        self.prefix = w.get("prefix") or None
        # the mix, from the file's own seed: identical for every --seed
        mix = np.random.default_rng(int(w.get("pool_seed", 0)))
        prompts = _lengths(w["prompt_tokens"], self.pool, mix)
        outputs = _lengths(w["output_tokens"], self.pool, mix)
        fractions = (np.arange(self.clients) + 0.5) / self.clients
        gaps = _gaps(self.arrival, self.pool, mix) if self.kind != "closed" else None
        self.prompt_lens, self.output_lens, self.gaps = prompts, outputs, gaps
        self.first_fraction = mix.permutation(fractions)
        if self.prefix:
            g = int(self.prefix["groups"])
            self.groups = mix.permutation(np.arange(self.pool) % g)
            self.turns = int(self.prefix.get("turns", 1))
        else:
            self.groups = np.full(self.pool, -1)
            self.turns = 1

    # -- what a request holds ---------------------------------------------
    def _ids(self, *key: int, n: int) -> list[int]:
        rng = np.random.default_rng([self.seed, 2, *key])
        return rng.integers(1, self.vocab, int(n)).tolist()

    def _piece_len(self, session: int, turn: int) -> int:
        return int(self.prompt_lens[(session * self.turns + turn) % self.pool])

    def tokens(self, spec: Spec) -> list[int]:
        """The prompt's token ids: the group's shared prefix, then one
        stretch for each turn so far, so turn t's prompt extends turn
        t-1's and a prefix cache can hit on it."""
        head = self._ids(3, spec.group, n=self.prefix["prefix_tokens"]) if spec.group >= 0 else []
        body: list[int] = []
        for t in range(spec.turn + 1):
            body += self._ids(4, spec.session, t, n=self._piece_len(spec.session, t))
        return head + body

    # -- the stream -------------------------------------------------------
    def _spec(self, client: int, index: int, session: int, turn: int, due: float) -> Spec:
        """Request `turn` of `session`; without sessions every request is
        turn 0 of a session of its own."""
        slot = (session * self.turns + turn) % self.pool
        group = int(self.groups[session % self.pool])
        n = sum(self._piece_len(session, t) for t in range(turn + 1))
        if group >= 0:
            n += int(self.prefix["prefix_tokens"])
        return Spec(index, client, n, int(self.output_lens[slot]), group, session, turn, due)

    def distinct(self) -> list[Spec]:
        """Every distinct request the mix cycles through, each session's
        turns included, whatever the arrival: what the engine has to hold."""
        return [self._spec(0, s * self.turns + t, s, t, 0.0)
                for s in range(self.pool) for t in range(self.turns)]

    def client_request(self, client: int, k: int) -> Spec:
        """Closed loop: the k-th request of a client. Its first asks for a
        fraction of its drawn output, so the clients are out of step when
        the window opens and requests finish all through it."""
        session, turn = divmod(k, self.turns)
        spec = self._spec(client, k, client + session * self.clients, turn, 0.0)
        if k == 0:
            frac = float(self.first_fraction[client])
            spec = dataclasses.replace(spec, output_len=max(2, int(round(spec.output_len * frac))))
        return spec

    def arrivals(self, horizon_s: float) -> list[Spec]:
        """Open loop: every request due before `horizon_s`, with its due
        time. The gaps cycle through the pool."""
        out: list[Spec] = []
        t, i = 0.0, 0
        while True:
            t += float(self.gaps[i % self.pool])
            if t >= horizon_s:
                return out
            session, turn = divmod(i, self.turns)
            out.append(self._spec(i % max(1, self.clients), i, session, turn, t))
            i += 1

    def describe(self) -> dict:
        """What was drawn, for the run's earlier lines."""
        q = lambda a: [int(x) for x in np.percentile(a, [0, 50, 90, 100])]  # noqa: E731
        d = {"arrival": self.arrival, "pool": self.pool,
             "prompt_tokens_min_p50_p90_max": q(self.prompt_lens),
             "output_tokens_min_p50_p90_max": q(self.output_lens)}
        if self.prefix:
            d["prefix"] = self.prefix
        return d
