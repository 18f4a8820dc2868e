"""Cells of kind ``llm_train``: hierarchical LLM training, one cloud round
per call of the program's jitted step (``repro.launch.train.make_trainer``).

Set-up builds the step with its parameters once, drives it from the seed
through the first ``check_steps`` rounds on distinct batches (the check's
program side, read from its state), and hands the same step and
parameters to the window, which runs whole rounds until the time is up,
keeping about ``AHEAD_S`` seconds of rounds dispatched ahead of the one
it waits for, so that a stall of the host does not idle the chip.
After the window the program's state is freed and the plain reference
follows the same first rounds from the same seed: the model of the
module the configuration names under ``"reference"``
(``spec.load_reference``) inside ``ref_hfl``'s round.

A configuration file sets the program's ``ArchConfig`` through
``ARCH_KEYS`` and then through its own ``"program_keys"``, which map
more of its keys to fields (a dotted name reaches a nested one, as
``"n_routed_experts": "moe.n_experts"``), so one value in the file feeds
both the program and the reference.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import compare
import ref_hfl
import spec
import traffic as traffic_gen

# configuration-file keys -> the program's ArchConfig fields
ARCH_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head", "intermediate_size": "d_ff",
    "vocab_size": "vocab", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "param_dtype": "param_dtype",
    "activation_dtype": "activ_dtype",
}


def _set_field(obj, path: list, value, full: str):
    """``obj`` with the field at ``path`` (nested dataclasses) set."""
    names = ({f.name for f in dataclasses.fields(obj)}
             if dataclasses.is_dataclass(obj) else set())
    if path[0] not in names:
        raise KeyError(f"program_keys: {full!r} names no field of "
                       f"{type(obj).__name__}")
    if len(path) > 1:
        value = _set_field(getattr(obj, path[0]), path[1:], value, full)
    return dataclasses.replace(obj, **{path[0]: value})


def arch_config(c: dict):
    """The program's ArchConfig for configuration file ``c``: the
    program's entry for ``c["program_arch"]`` with every size set from
    the file, by ``ARCH_KEYS`` and then by ``c["program_keys"]``."""
    from repro.configs import get_config
    cfg = dataclasses.replace(
        get_config(c["program_arch"]),
        **{f: c[k] for k, f in ARCH_KEYS.items() if k in c})
    for key, field in c.get("program_keys", {}).items():
        cfg = _set_field(cfg, field.split("."), c[key], field)
    return cfg


# seconds of rounds the window keeps dispatched ahead of the one it
# waits for
AHEAD_S = 6.0


def tokens_per_round(t: dict) -> int:
    """Trained tokens of one cloud round: every replica trains
    gamma1 * gamma2 epochs over its rows."""
    return (t["gamma1"] * t["gamma2"] * int(np.prod(t["replicas"]))
            * t["seqs_per_replica"] * t["seq_len"])


def _rep0_leaves(params) -> dict:
    """Replica (0, 0, 0) of the program's parameters, per layer."""
    return compare.split_layers(compare.flat_leaves(
        jax.tree.map(lambda a: a[0, 0, 0], params)))


@jax.jit
def _delta_norms(params, p0):
    """Per-leaf norms of replica (0, 0, 0)'s change since ``p0``."""
    now = _rep0_leaves(params)
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        now[k].astype(jnp.float32) - p0[k].astype(jnp.float32))))
        for k in now}


@jax.jit
def _marker(params):
    """One element of the step's output: ready when the round that made
    ``params`` has ended, and still readable after the next round has
    taken (donated) ``params``."""
    return jax.tree.leaves(params)[0].ravel()[0]


class LLMCell:
    """One run of an ``llm_train`` cell on ``devices``."""

    def __init__(self, cell, seed: int, devices, make_trainer=None):
        self.cell, self.seed, self.devices = cell, seed, list(devices)
        self.t = cell.traffic
        self.c = cell.config
        self.init_seed = traffic_gen.sub_seed(seed, 0)
        self._make_trainer = make_trainer
        self.rounds_done = 0

    # -- set-up --------------------------------------------------------
    def setup(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import mesh as mesh_lib
        from repro.launch import train
        # a missing or unknown reference fails here, not after the window
        spec.load_reference(self.c.get("reference"))
        t = self.t
        make = self._make_trainer or train.make_trainer
        self.step, params, mesh = make(
            arch_config(self.c), tuple(t["replicas"]),
            devices=self.devices, seed=self.init_seed, lr=t["lr"],
            mb_per_epoch=t["microbatches"], g1=t["gamma1"], g2=t["gamma2"],
            remat=t["remat"], attn_chunk=t["attn_chunk"])
        self.t_built = time.perf_counter()
        bsh = NamedSharding(mesh, P(mesh_lib.REPLICA_AXES))
        self.host_batches = traffic_gen.token_batches(
            self.seed, t, self.c["vocab_size"])
        self.batches = [jax.device_put(b, bsh) for b in self.host_batches]
        p0 = jax.device_get(_rep0_leaves(params))
        self.prog_norms = {}
        marks = {}
        for k in range(1, t["check_steps"] + 1):
            params = self.step(params, self.batches[k - 1])
            if k in (1, t["check_steps"]):
                self.prog_norms[k] = {
                    n: float(v) for n, v in jax.device_get(
                        _delta_norms(params, jax.device_put(p0))).items()}
                marks[k] = time.perf_counter()
        jax.block_until_ready(_marker(params))
        # rounds 2.. of the check, each with its wait, estimate a round
        later = t["check_steps"] - 1
        self.round_est = ((marks[t["check_steps"]] - marks[1]) / later
                          if later else marks[1] - self.t_built)
        self.ahead = max(1, math.ceil(AHEAD_S / self.round_est))
        self.params = params
        self.rounds_done = t["check_steps"]
        del p0

    # -- the measured window ---------------------------------------------
    def window(self, seconds: float) -> dict:
        """Whole rounds, ``self.ahead`` of them in flight. Once the
        rounds sent would fill ``seconds``, nothing more is sent; the
        window waits for every round sent and reads the clock after
        that wait: all of their tokens over all of that time."""
        n = len(self.batches)
        params, rounds = self.params, 0
        inflight = collections.deque()
        ends = []

        def wait_oldest():
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(inflight.popleft())
            ends.append(time.perf_counter())

        t0 = time.perf_counter()
        while (time.perf_counter() - t0
               + len(inflight) * self.round_est < seconds):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                params = self.step(params,
                                   self.batches[self.rounds_done % n])
                inflight.append(_marker(params))
            rounds += 1
            self.rounds_done += 1
            if len(inflight) > self.ahead:
                wait_oldest()
        while inflight:
            wait_oldest()
        self.params = params
        return {"rounds": rounds, "elapsed_s": ends[-1] - t0,
                "work": rounds * tokens_per_round(self.t),
                "round_s": [float(x) for x in np.diff([t0] + ends)]}

    def rates(self, counts: dict) -> dict:
        """Trained tokens per second per chip over the window."""
        return {"train_tokens_per_s": counts["work"] / counts["elapsed_s"]
                / len(self.devices)}

    def failed_rounds(self, rounds: int) -> int:
        """All of the window's rounds failed when its state is not
        finite at the end."""
        finite = all(bool(jnp.all(jnp.isfinite(a)))
                     for a in jax.tree.leaves(self.params))
        return 0 if finite else rounds

    def release(self):
        self.params = self.batches = self.step = None

    # -- the check -------------------------------------------------------
    def reference_norms(self, dtype=jnp.float32, fault: str = "") -> dict:
        """Per-leaf change norms of the reference over the first rounds,
        held in ``dtype``; ``fault`` plants one of the check's faults in
        the reference put in the program's place."""
        return reference_norms(self.c, self.t, self.init_seed,
                               self.host_batches, dtype, fault)

    def check(self, ref: dict | None = None) -> dict:
        ref = ref or self.reference_norms()
        return judge_numbers(self.prog_norms, ref, self.cell.limits,
                             self.t["check_steps"])


def judge_numbers(prog: dict, ref: dict, limits: dict, steps: int) -> dict:
    keep = compare.kept_leaves(ref[1])
    out = {}
    for k, name in ((1, "delta1_gap"), (steps, f"delta{steps}_gap")):
        gap, leaf = compare.norm_gap(prog[k], ref[k], keep)
        out[name] = {"value": gap, "limit": limits[name], "leaf": leaf}
    return out


def reference_norms(c: dict, t: dict, init_seed: int, host_batches: list,
                    dtype=jnp.float32, fault: str = "") -> dict:
    """The reference's per-leaf change norms after round 1 and after the
    last checked round, on the run's first batches."""
    ref = spec.load_reference(c.get("reference"))
    with jax.default_matmul_precision("highest"):
        p0 = jax.jit(lambda k: ref.init(k, c))(
            jax.random.PRNGKey(init_seed))
        p0 = jax.tree.map(lambda a: a.astype(dtype), p0)
        sgd = ref_hfl.make_sgd_step(ref.loss, c, t["lr"], dtype)
        model = jax.tree.map(jnp.copy, p0)
        base = compare.split_layers(compare.flat_leaves(p0))
        out = {}
        replicas, spr, mb = (tuple(t["replicas"]), t["seqs_per_replica"],
                             t["microbatches"])
        for k in range(1, t["check_steps"] + 1):
            b = host_batches[k - 1]
            rows = (jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
            if fault == "half_batch":
                # each replica keeps the first half of its rows
                n_rep = int(np.prod(replicas))
                keep = np.concatenate([np.arange(r * spr, r * spr + spr // 2)
                                       for r in range(n_rep)])
                rows = tuple(a[keep] for a in rows)
                mb_k = max(1, mb // 2)
            else:
                mb_k = mb
            if fault == "no_aggregation":
                # replica (0, 0, 0) alone: its rows, no edge or cloud mean
                rows = tuple(a[:spr // (mb // mb_k)] for a in rows)
                model = ref_hfl.hfl_round(model, rows, (1, 1, 1),
                                          t["gamma1"], t["gamma2"], mb_k,
                                          sgd, dtype)
            else:
                model = ref_hfl.hfl_round(model, rows, replicas,
                                          t["gamma1"], t["gamma2"], mb_k,
                                          sgd, dtype)
            if k in (1, t["check_steps"]):
                now = compare.split_layers(compare.flat_leaves(model))
                out[k] = {n: float(jnp.sqrt(jnp.sum(jnp.square(
                    now[n].astype(jnp.float32) - base[n].astype(jnp.float32)))))
                    for n in base}
    return out
