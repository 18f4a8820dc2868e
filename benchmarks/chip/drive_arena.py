"""Cells of kind ``arena_env``: the Arena environment in real mode, one
``HFLEnv.step(action)`` per environment step (a synchronous cloud round
of ``repro.core.hfl.make_cloud_round`` plus the cost simulation, the
accuracy, the reward and the PCA state).

Set-up builds the environment from the seed, resets it (its first round
and the PCA fit) and drives it through the first ``check_steps`` steps of
the cell's fixed action stream (the check's program side), then hands
the same environment to the window. In the window an episode that ends
is reset, as a PPO rollout does, and the reset counts as a step. After
the window the plain reference (``ref_cnn``) replays the reset round and
the first steps from the seed.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import compare
import ref_cnn
import traffic as traffic_gen

# configuration-file keys that are EnvConfig fields
ENV_KEYS = ("task", "n_devices", "n_edges", "n_local", "batch_size", "lr",
            "data_scheme", "threshold_time", "epsilon", "gamma_max",
            "n_pca", "use_profiling")
RESET_GAMMA = 2      # the environment's reset round (Algorithm 1, l. 3-5)


def env_config(c: dict, seed: int):
    from repro.sim import EnvConfig
    return EnvConfig(mode="real", seed=seed, **{k: c[k] for k in ENV_KEYS})


def leaf_norms(a: dict, b: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(a[k], np.float64)
                                    - np.asarray(b[k], np.float64)))
            for k in a}


class ArenaCell:
    """One run of an ``arena_env`` cell on one chip."""

    def __init__(self, cell, seed: int, devices, env_factory=None):
        self.cell, self.c, self.t = cell, cell.config, cell.traffic
        self.env_seed = traffic_gen.sub_seed(seed, 0) & 0x3FFFFFFF
        self.devices = list(devices)
        self.actions = traffic_gen.action_stream(self.t, self.c["n_edges"])
        self._env_factory = env_factory
        self.steps_done = 0

    def _samples(self, g1, g2) -> int:
        """Useful training samples of one round: every device trains
        g1 * g2 epochs of whole minibatches of its edge."""
        per_epoch = (self.c["n_local"] // self.c["batch_size"]
                     * self.c["batch_size"])
        ea = self.env.edge_assign
        return int(np.sum(np.asarray(g1)[ea] * np.asarray(g2)[ea])) * per_epoch

    def _reset_samples(self) -> int:
        g = np.full(self.c["n_edges"], RESET_GAMMA)
        return self._samples(g, g)

    def _gammas(self, action):
        m = self.c["n_edges"]
        a = np.clip(np.round(action), 1, self.c["gamma_max"]).astype(int)
        return a[:m], a[m:]

    # -- set-up --------------------------------------------------------
    def setup(self):
        from repro.sim import HFLEnv
        make = self._env_factory or HFLEnv
        with jax.default_device(self.devices[0]):
            self.env = env = make(env_config(self.c, self.env_seed))
            env.reset()
            self.models = {0: self._global_host()}
            self.accs = {}
            for k in range(1, self.t["check_steps"] + 1):
                _, _, done, info = env.step(self.actions[k - 1])
                if done:
                    raise RuntimeError("episode ended inside the check steps")
                self.models[k] = self._global_host()
                self.accs[k] = float(info["acc"])
        self.steps_done = self.t["check_steps"]

    def _global_host(self) -> dict:
        return {k: np.asarray(v) for k, v in
                compare.flat_leaves(jax.device_get(self.env.global_model)).items()}

    # -- the measured window ---------------------------------------------
    def window(self, seconds: float) -> dict:
        env, n = self.env, len(self.actions)
        steps = samples = resets = 0
        self.bad = 0
        epochs0 = env.epoch_counts["computed"]
        t0 = time.perf_counter()
        ends = []
        with jax.default_device(self.devices[0]):
            while True:
                action = self.actions[self.steps_done % n]
                with jax.profiler.TraceAnnotation("bench.step"):
                    _, r, done, info = env.step(action)
                samples += self._samples(*self._gammas(action))
                if not (np.isfinite(r) and np.isfinite(info["acc"])):
                    self.bad += 1
                steps += 1
                self.steps_done += 1
                if done:
                    with jax.profiler.TraceAnnotation("bench.reset"):
                        env.reset()
                    samples += self._reset_samples()
                    steps += 1
                    resets += 1
                ends.append(time.perf_counter())
                if ends[-1] - t0 >= seconds:
                    break
        elapsed = ends[-1] - t0
        # the device-epochs the rounds ran: each step's
        # info["epochs_computed"], and the reset rounds'
        computed = ((env.epoch_counts["computed"] - epochs0)
                    * (self.c["n_local"] // self.c["batch_size"])
                    * self.c["batch_size"])
        return {"rounds": steps, "elapsed_s": elapsed, "work": steps,
                "useful_samples": samples, "resets": resets,
                "useful_share": samples / computed,
                "round_s": [float(x) for x in np.diff([t0] + ends)]}

    def rates(self, counts: dict) -> dict:
        return {"env_steps_per_s": counts["work"] / counts["elapsed_s"]}

    def failed_rounds(self, rounds: int) -> int:
        return self.bad

    def release(self):
        env = self.env
        # keep the environment's inputs for the reference; free its state
        self.inputs = {"x": env.fed.x, "y": env.fed.y,
                       "test_x": env.fed.test_x, "test_y": env.fed.test_y,
                       "sizes": np.asarray(env.fed.device_sizes()),
                       "edge_assign": np.asarray(env.edge_assign)}
        self.env = None

    # -- the check -------------------------------------------------------
    def reference_models(self, dtype=jnp.float32, fault: str = "") -> dict:
        return reference_models(self.c, self.t, self.env_seed, self.actions,
                                self.inputs, dtype, fault)

    def check(self, ref: dict | None = None) -> dict:
        ref = ref or self.reference_models()
        return judge_numbers(self.models, self.accs, ref, self.inputs,
                             self.cell.limits, self.t["check_steps"])


def judge_numbers(models: dict, accs: dict, ref: dict, inputs: dict,
                  limits: dict, steps: int) -> dict:
    """The compared numbers: worst-leaf gaps of the global model's change
    over the first step and over all checked steps, and the largest gap
    between the accuracy the environment reported and the reference's
    evaluation of the environment's own global model."""
    prog = {k: leaf_norms(models[k], models[0]) for k in (1, steps)}
    refn = {k: leaf_norms(ref["models"][k], ref["models"][0])
            for k in (1, steps)}
    keep = compare.kept_leaves(refn[1])
    out = {}
    for k, name in ((1, "delta1_gap"), (steps, f"delta{steps}_gap")):
        gap, leaf = compare.norm_gap(prog[k], refn[k], keep)
        out[name] = {"value": gap, "limit": limits[name], "leaf": leaf}
    with jax.default_matmul_precision("highest"):
        acc_fn = jax.jit(ref_cnn.accuracy)
        acc_gap = max(abs(accs[k] - float(acc_fn(
            {n: jnp.asarray(v) for n, v in models[k].items()},
            inputs["test_x"], inputs["test_y"]))) for k in accs)
    out["acc_gap"] = {"value": acc_gap, "limit": limits["acc_gap"]}
    return out


def reference_models(c: dict, t: dict, env_seed: int, actions, inputs: dict,
                     dtype=jnp.float32, fault: str = "") -> dict:
    """The reference's global model after the reset round (0) and after
    each checked step, as host arrays."""
    m, gmax = c["n_edges"], c["gamma_max"]
    batch_used = c["batch_size"] // 2 if fault == "half_batch" else 0
    with jax.default_matmul_precision("highest"):
        epoch = ref_cnn.make_epoch(c["lr"], c["batch_size"], dtype,
                                   batch_used)
        model = ref_cnn.init(jax.random.PRNGKey(env_seed + 1000))
        key = jax.random.PRNGKey(env_seed)
        gs = [(np.full(m, RESET_GAMMA), np.full(m, RESET_GAMMA))]
        for k in range(t["check_steps"]):
            a = np.clip(np.round(actions[k]), 1, gmax).astype(int)
            gs.append((a[:m], a[m:]))
        out = {}
        for k, (g1, g2) in enumerate(gs):
            key, sub = jax.random.split(key)
            model = ref_cnn.cloud_round(
                model, inputs["x"], inputs["y"], inputs["sizes"],
                inputs["edge_assign"], g1, g2, sub, epoch, gmax, gmax, dtype,
                aggregate=fault != "no_aggregation")
            out[k] = {n: np.asarray(v, np.float32) for n, v in
                      compare.flat_leaves(jax.device_get(model)).items()}
    return {"models": out}
