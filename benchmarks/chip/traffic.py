"""The general generators: every input of a run is drawn here from
``--seed`` and the parameters of the cell's traffic file.

The same seed gives the same inputs. Every seed gets the same sizes:
the seed changes only which tokens and which actions, never how much
work a round does.
"""
from __future__ import annotations

import numpy as np


def sub_seed(seed: int, *path: int) -> int:
    """A 31-bit seed derived from ``seed`` (any whole number, also past
    32 bits) and a path of indices."""
    ss = np.random.SeedSequence([int(seed) % (2 ** 63)] + [int(p) for p in path])
    return int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


def token_batch(seed: int, batch: int, seq: int, vocab: int,
                zipf_a: float) -> dict:
    """One language-model batch: Zipf-distributed token ids clipped to
    the vocabulary, labels shifted by one position. (The program's
    ``data.synthetic.token_batch``, copied so the yardstick stays.)"""
    rng = np.random.default_rng(seed)
    z = rng.zipf(zipf_a, size=(batch, seq + 1))
    toks = np.minimum(z, vocab - 1).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def token_batches(seed: int, traffic: dict, vocab: int) -> list:
    """The run's distinct batches, in the order the rounds use them."""
    n_rep = int(np.prod(traffic["replicas"]))
    batch = n_rep * traffic["seqs_per_replica"]
    return [token_batch(sub_seed(seed, 1, i), batch, traffic["seq_len"],
                        vocab, traffic["zipf_a"])
            for i in range(traffic["distinct_batches"])]


def action_stream(traffic: dict, n_edges: int) -> np.ndarray:
    """The fixed per-edge (gamma1, gamma2) stream of an environment cell,
    shape (n_actions, 2 * n_edges): gamma1 of every edge, then gamma2,
    drawn uniformly from [lo, hi] from the traffic file's own stream
    seed, so every run seed sees the same actions."""
    rng = np.random.default_rng(traffic["action_seed"])
    lo, hi = traffic["gamma_range"]
    return rng.integers(lo, hi + 1, size=(traffic["n_actions"], 2 * n_edges))
