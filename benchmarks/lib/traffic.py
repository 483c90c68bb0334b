"""The one general traffic generator: a mix is a data file of parameters.

Every seed is given the same set of sizes in another order.  A closed
loop draws its requests from a *deck*: ``deck`` (prompt, answer) pairs
whose prompt lengths follow ``prompt_weights`` exactly (largest
remainder) and whose answer lengths are the deck's quantile midpoints of
the stated distribution, paired the same way for every seed; the seed
shuffles the order of the deck, round after round.  So two runs of different
seeds do the same work up to the order, and the spread between runs says
something about the system and not about the draw.

With ``stagger_first`` the callers' first answers are cut short by evenly
spread fractions, so a short ramp leaves the pool as a long-running one
is: every caller somewhere inside a request.

Sessions (``turns``) make each caller hold a conversation: every turn's
prompt is the shared prefix, the history so far (earlier user tokens and
the served answers) and new user tokens, cut to a multiple of
``snap_to``.
"""
from __future__ import annotations

import math

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def quantile_lengths(dist: dict, n: int) -> list:
    """``n`` whole lengths at the quantile midpoints of ``dist``."""
    lo, hi = float(dist["low"]), float(dist["high"])
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "log_uniform":
        vals = [math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
                for q in qs]
    elif dist["dist"] == "uniform":
        vals = [lo + q * (hi - lo) for q in qs]
    elif dist["dist"] == "fixed":
        vals = [lo] * n
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return [int(round(v)) for v in vals]


def weighted_counts(weights, n: int) -> list:
    """``n`` split by ``weights``, largest remainder."""
    total = float(sum(weights))
    exact = [w / total * n for w in weights]
    counts = [int(math.floor(e)) for e in exact]
    by_rest = sorted(range(len(weights)),
                     key=lambda i: exact[i] - counts[i], reverse=True)
    for i in by_rest[:n - sum(counts)]:
        counts[i] += 1
    return counts


def spread_by_weight(values, weights, n: int) -> list:
    """``n`` of ``values`` in their ``weights``' shares (largest
    remainder), dealt as evenly as the shares allow: at every place the
    value furthest behind its share comes next."""
    counts = weighted_counts(weights, n)
    dealt, out = [0] * len(values), []
    for i in range(n):
        k = max(range(len(values)), key=lambda j: (
            counts[j] * (i + 1) / n - dealt[j], -j))
        dealt[k] += 1
        out.append(values[k])
    return out


class ClosedLoop:
    """Requests for ``callers`` callers that each wait for their reply."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = int(vocab)
        self.callers = int(mix["callers"])
        self._order = _rng(seed, 1)
        self._ids = _rng(seed, 2)
        n = int(mix["deck"])
        # the deck is the same (prompt, answer) pairs for every seed: the
        # answers in rising order meet the prompt lengths dealt evenly by
        # weight, so long answers meet every prompt length in its share
        self._pairs = list(zip(
            spread_by_weight(mix["prompt_lengths"], mix["prompt_weights"], n),
            quantile_lengths(mix["new_tokens"], n)))
        self._deck: list = []
        self.shared = self._ids.integers(
            0, self.vocab, int(mix.get("shared_prefix_tokens", 0))
        ).astype(np.int32)
        turns = mix.get("turns")
        self._turns = (int(turns["low"]), int(turns["high"])) if turns \
            else None
        self._snap = int(mix.get("snap_to", 1))
        self._sessions: dict = {}
        self._sampling = mix.get("sampling")
        # ``stagger_first``: every caller's first answer is cut to a
        # fraction of its length, the fractions evenly spread over (0, 1)
        # and dealt by the seed, so the callers start out of step with
        # each other, as far into their requests as a running pool is
        self._first = list(self._order.permutation(
            [(i + 0.5) / self.callers for i in range(self.callers)])
        ) if mix.get("stagger_first") else None
        self._begun: set = set()

    def _draw(self):
        if not self._deck:
            self._deck = [self._pairs[i] for i in self._order.permutation(
                len(self._pairs))]
        return self._deck.pop()

    def _sampled(self):
        """None for a greedy request, else the sampling parameters: a
        ``greedy_share`` of a sampled mix stays greedy, because only
        greedy tokens can be held to the reference."""
        s = self._sampling
        if not s or self._order.random() < float(s.get("greedy_share", 0)):
            return None
        return {"top_p": float(s.get("top_p", 1.0)),
                "temperature": float(s.get("temperature", 1.0)),
                "seed": int(self._order.integers(0, 2**31 - 1))}

    def next_request(self, caller: int, last_answer=None):
        """(prompt ids, max_new_tokens, sampling or None) for
        ``caller``'s next request; ``last_answer`` is what its previous
        request was served."""
        n_new, n_out = (int(x) for x in self._draw())
        if self._first is not None and caller not in self._begun:
            self._begun.add(caller)
            n_out = max(2, int(round(n_out * self._first.pop())))
        fresh = self._ids.integers(0, self.vocab, n_new).astype(np.int32)
        sampled = self._sampled()
        if self._turns is None:
            return np.concatenate([self.shared, fresh]), n_out, sampled
        s = self._sessions.get(caller)
        if s is not None and last_answer is not None:
            s["history"] = np.concatenate(
                [s["history"], np.asarray(last_answer, np.int32)])
        if s is None or s["left"] == 0:
            lo, hi = self._turns
            s = {"left": int(self._order.integers(lo, hi + 1)),
                 "history": np.zeros((0,), np.int32)}
            self._sessions[caller] = s
        s["left"] -= 1
        prompt = np.concatenate([self.shared, s["history"], fresh])
        prompt = prompt[:max(self._snap, prompt.size // self._snap
                             * self._snap)]
        s["history"] = prompt[self.shared.size:]
        return prompt, n_out, sampled


def train_pool(mix: dict, vocab: int, num_labels: int, seed: int):
    """(ids [pool, batch, seq] int32, labels [pool, batch] int32), made on
    the device from the seed in one jitted call: every row differs.

    With ``labels`` the mix states every batch's labels, row by row, and
    the seed draws the tokens alone.  A batch of random labels on a model
    that knows nothing yet has a gradient that all but cancels (the rows'
    p - y sum to nearly nought), by an amount the seed decides: the same
    rounding then reads as a hundredth of the gradient on one seed and a
    thousandth on the next.  Stated labels give every seed the same
    sum."""
    import jax
    import jax.numpy as jnp

    pool, batch, seq = int(mix["pool"]), int(mix["batch"]), int(mix["seq"])
    stated = mix.get("labels")
    if stated is not None and (len(stated) != batch or not all(
            0 <= int(y) < num_labels for y in stated)):
        raise ValueError(f"labels must be {batch} of 0..{num_labels - 1}")

    @jax.jit
    def make(key):
        a, b = jax.random.split(key)
        ids = jax.random.randint(a, (pool, batch, seq), 0, vocab, jnp.int32)
        if stated is not None:
            return ids, jnp.tile(jnp.asarray(stated, jnp.int32), (pool, 1))
        return ids, jax.random.randint(b, (pool, batch), 0, num_labels,
                                       jnp.int32)

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                             (seed >> 31) + 7919)
    return make(key)
