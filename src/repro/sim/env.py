"""The HFL environment the DRL agent interacts with (paper Fig. 5 + Alg. 1).

Two fidelity modes sharing one interface:

* ``mode="real"`` — devices actually train the testbed CNN on federated
  synthetic MNIST/CIFAR shards via ``repro.core.hfl`` (vmapped); accuracy
  is measured on the held-out test set. This is the faithful reproduction
  path (used by the paper-table benchmarks at reduced scale — 1 CPU core
  vs. the paper's 50 Raspberry Pis).
* ``mode="analytic"`` — accuracy evolves by a saturating-progress model
  with non-IID drift and staleness penalties calibrated to the real mode;
  time/energy come from the same hardware simulator. Used to train the
  PPO agent for the paper's full episode counts (1500/700) at tractable
  cost; EXPERIMENTS.md reports both modes.

One ``HFLEnv`` step = one cloud aggregation round driven by the
per-edge action (γ1, γ2) — exactly Algorithm 1's inner loop, with the
synchronous barrier ``t_use = max_j t_edge_j``.

In the profiler trace the environment's host work sits in spans: a
step is ``hfl.step`` and a reset ``hfl.reset``, and inside them
``hfl.costs`` (mobility and the hardware cost draws), ``hfl.round``
(preparing and dispatching the cloud round), ``hfl.acc`` (the accuracy,
which waits for the round), ``hfl.state`` and, in a reset,
``hfl.fit_pca``; the reward, the health check and the bookkeeping are
the step's own. Its device work outside the round (the accuracy and the
PCA projection) is scoped ``hfl.eval``. ``epoch_counts`` counts the
device-epochs real-mode rounds compute (``hfl.round_epochs``: every
device runs each epoch of the round's loops) and those they keep
(Σ γ1·γ2 over participating devices), reset rounds included.

``AsyncHFLEnv`` (below) removes that barrier: edges run on their own
clocks through the event-driven runtime (``repro.runtime``), the cloud
aggregates a staleness-decayed update buffer, and one env step = one
edge upload event (2-dim per-edge action). DESIGN.md §4 has the design
notes; EXPERIMENTS.md §Calibration the async analytic-mode update.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import hfl, pca, profiling, reward as reward_mod, state as state_mod
from repro.data import federated, synthetic
from repro.models import model as model_mod
from repro.sim import hardware
from repro.telemetry.health import HealthConfig, HealthMonitor


@dataclasses.dataclass
class EnvConfig:
    task: str = "mnist"              # mnist | cifar
    mode: str = "real"               # real | analytic
    n_devices: int = 50
    n_edges: int = 5
    n_local: int = 1200              # samples per device (paper: 1200/1000)
    batch_size: int = 32
    lr: float = 0.003                # paper: 0.003 MNIST, 0.01 Cifar
    data_scheme: str = "label2"      # iid | labelK | dirichlet
    dirichlet_alpha: float = 0.5
    threshold_time: float = 3000.0   # T (paper: 3000 s MNIST, 12000 s Cifar)
    epsilon: float = 0.002           # reward energy weight
    gamma_max: int = 8               # action upper bound per frequency
    n_pca: int = 6
    edge_regions: Optional[tuple] = None   # default 3x cn + 2x us (paper)
    use_profiling: bool = True       # cluster devices by capability
    seed: int = 0
    # device mobility (paper §2.3): per-round probability that a device
    # changes its interference profile (app started/stopped, moved) and
    # re-cluster cadence (profiling module's periodic re-cluster, §3.1)
    churn_prob: float = 0.0
    recluster_every: int = 0
    # multi-host flat bank: the aggregation context (hfl.AggContext)
    # every round/flush/resync runs under — build it once with
    # hfl.AggContext.for_mesh(launch.mesh.make_bank_mesh(...)); None =
    # single chip. ``mesh`` is the deprecated one-cycle spelling (a
    # bare mesh, wrapped into a context at env construction).
    agg: Optional[object] = None
    mesh: Optional[object] = None
    # observability (repro.telemetry; DESIGN.md §7): True builds the
    # async env with an enabled Telemetry facade (trace recorder +
    # metrics registry). Pure observation — enabled vs disabled is
    # bitwise-identical (tests/test_telemetry.py).
    telemetry: bool = False
    # per-run health monitors (repro.telemetry.health; DESIGN.md §8):
    # True attaches a HealthMonitor with the default HealthConfig —
    # NaN/Inf guard, divergence + flush-stall detection — surfacing
    # events in info["health"] and the run ledger. Observation only:
    # health-on vs -off is bitwise-identical (tests/test_ledger.py).
    health: bool = False
    # analytic-mode calibration
    a_max: float = 0.80
    a_rate: float = 0.016            # per-local-epoch progress rate
    drift_coef: float = 0.25         # non-IID drift per unbalanced epoch
    stale_coef: float = 0.015        # large-γ2 staleness penalty
    noise: float = 0.004
    cov_pow: float = 0.5             # async: partial-buffer coverage
                                     # exponent (EXPERIMENTS.md §Calib.)

    def fixup(self) -> "EnvConfig":
        if self.task == "cifar" and self.threshold_time == 3000.0:
            # paper: T=12000 s, lr=0.01, eps=0.03. Our simulator's E(k) is
            # the 50-device TOTAL (~10x the paper's testbed scale), so the
            # reward weight is rescaled to keep the paper's accuracy-vs-
            # energy pressure ratio (see EXPERIMENTS.md scale note).
            return dataclasses.replace(self, threshold_time=12000.0,
                                       lr=0.01, epsilon=0.004,
                                       n_local=1000)
        return self


class HFLEnv:
    """Gym-ish: reset() -> state; step(a) -> (state, reward, done, info)."""

    def __init__(self, cfg: EnvConfig, health=None):
        cfg = cfg.fixup()
        self.cfg = cfg
        # per-run health monitors: an explicit HealthMonitor (or a bare
        # HealthConfig) wins; else cfg.health toggles the defaults on.
        # None = disabled — the health-off code path is unchanged.
        if health is None and cfg.health:
            health = HealthMonitor()
        elif isinstance(health, HealthConfig):
            health = HealthMonitor(health)
        self.health = health
        # one AggContext carries the mesh / placement / donation policy
        # for every aggregation this env runs; cfg.mesh is the
        # deprecated spelling and resolves here once (with the same
        # one-cycle DeprecationWarning the hfl entry points emit)
        self.agg_ctx = hfl._resolve_ctx(cfg.agg, cfg.mesh, "EnvConfig")
        self.rng = np.random.default_rng(cfg.seed)
        self.profiles = hardware.DeviceProfiles.sample(
            self.rng, cfg.n_devices, task=cfg.task)
        regions = cfg.edge_regions or tuple(
            ["cn"] * (cfg.n_edges - cfg.n_edges // 2)
            + ["us"] * (cfg.n_edges // 2))
        self.comm = hardware.CommModel(list(regions), task=cfg.task)
        # ---- topology: profiling module or round-robin -------------------
        if cfg.use_profiling:
            self.edge_assign = profiling.cluster_devices(
                self.profiles, cfg.n_edges, seed=cfg.seed)
        else:
            self.edge_assign = np.arange(cfg.n_devices) % cfg.n_edges
        self._edge_assign_j = jnp.asarray(self.edge_assign)
        # ---- task / data --------------------------------------------------
        if cfg.mode == "real":
            if cfg.task == "mnist":
                train, test = synthetic.synth_mnist(
                    n_train=max(20000, cfg.n_devices * cfg.n_local),
                    n_test=2000, seed=cfg.seed)
                self._init_fn = model_mod.mnist_cnn_init
                self._apply_fn = model_mod.mnist_cnn_apply
            else:
                train, test = synthetic.synth_cifar(
                    n_train=max(20000, cfg.n_devices * cfg.n_local),
                    n_test=2000, seed=cfg.seed)
                self._init_fn = model_mod.cifar_cnn_init
                self._apply_fn = model_mod.cifar_cnn_apply
            self.fed = federated.make_federated(
                train, test, cfg.n_devices, cfg.n_local,
                scheme=cfg.data_scheme, seed=cfg.seed,
                alpha=cfg.dirichlet_alpha)
            loss_fn = lambda p, b: model_mod.cnn_loss(self._apply_fn, p, b)
            self._loss_fn = loss_fn       # AsyncHFLEnv builds edge rounds
            # already jit-compiled; donates the bank buffer per round.
            # With a sharded context the round runs under GSPMD (bank
            # rows split over the mesh; see flatbank.ShardedBankSpec).
            self._cloud_round = hfl.make_cloud_round(
                loss_fn, cfg.lr, cfg.batch_size, cfg.n_edges,
                cfg.gamma_max, cfg.gamma_max, ctx=self.agg_ctx)
            if self.agg_ctx.sharded:
                # pin the federated data shards to the bank layout once
                # so no round re-ships (or replicates) the full dataset
                self.fed.x = self.agg_ctx.place_rows(self.fed.x)
                self.fed.y = self.agg_ctx.place_rows(self.fed.y)
            self._acc_fn = jax.jit(jax.named_scope("hfl.eval")(
                lambda p, x, y: model_mod.cnn_accuracy(
                    self._apply_fn, p, {"x": x, "y": y})))
        else:
            # analytic mode still needs a (tiny) parameter vector so the
            # PCA state path exercises the real machinery
            self._init_fn = model_mod.mnist_cnn_init
            self.fed = None
        self.model_dim_mb = hardware.MODEL_MB[cfg.task]
        # per-edge non-IID severity proxy for analytic drift (label overlap)
        self._edge_sizes = np.array(
            [np.sum(self.edge_assign == j) * cfg.n_local
             for j in range(cfg.n_edges)], np.float32)
        self.episode = 0
        self._key = jax.random.PRNGKey(cfg.seed)
        self.epoch_counts = {"computed": 0, "useful": 0}
        self._round_epochs = (0, 0)

    # ------------------------------------------------------------------
    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def reset(self) -> np.ndarray:
        with TraceAnnotation("hfl.reset"):
            return self._reset()

    def _reset(self) -> np.ndarray:
        cfg = self.cfg
        self.k = 0
        self.t_re = cfg.threshold_time
        self.acc = 0.1
        self.total_energy = 0.0
        self.energy_hist = []
        self.acc_hist = []
        self.time_hist = []
        self.episode += 1
        if self.health is not None:
            self.health.reset()
        key = jax.random.PRNGKey(cfg.seed + 1000)  # same w(0) each episode
        if cfg.mode == "real":
            self.bank = hfl.init_bank(self._init_fn, key, cfg.n_devices)
            # start the episode with the bank already row-sharded so the
            # first round never materializes it on one chip (identity on
            # a single-chip context)
            self.bank = self.agg_ctx.place_bank(self.bank)
            self.global_model = hfl.bank_select(self.bank, 0)
            self.edge_models = jax.tree.map(
                lambda a: jnp.stack([a] * cfg.n_edges),
                self.global_model)
        else:
            p0 = self._init_fn(key)
            self.global_model = p0
            self.edge_models = jax.tree.map(
                lambda a: jnp.stack([a] * cfg.n_edges), p0)
            self._edge_acc = np.full(cfg.n_edges, 0.1, np.float32)
        # Algorithm 1 line 3-5: one fixed-frequency round, fit PCA
        g0 = np.full(cfg.n_edges, 2, np.int64)
        h_edges, t_use, e_tot = self._run_round(g0, g0)
        self._fit_pca()
        self.t_re -= t_use
        self.k = 1
        self._h_edges = h_edges
        return self._state()

    def _fit_pca(self):
        # op by op, as it always ran: under jit XLA rounds the Gram
        # matrix differently and eigh may return a component with the
        # opposite sign, which would change the agent's state
        with TraceAnnotation("hfl.fit_pca"):
            self.pca_state = pca.fit(state_mod.model_matrix(
                self.global_model, self.edge_models), self.cfg.n_pca)

    # ------------------------------------------------------------------
    def _run_round(self, g1: np.ndarray, g2: np.ndarray,
                   participate: Optional[np.ndarray] = None):
        """Executes one cloud round; returns (h_edges (M,3), t_use, E)."""
        cfg = self.cfg
        m = cfg.n_edges
        with TraceAnnotation("hfl.costs"):
            # --- device mobility --------------------------------------------
            if cfg.churn_prob > 0:
                moved = self.rng.random(cfg.n_devices) < cfg.churn_prob
                if moved.any():
                    self.profiles.cpu_usage[moved] = self.rng.choice(
                        [0.1, 0.2, 0.3, 0.4, 0.5], size=int(moved.sum()))
                if (cfg.recluster_every and cfg.use_profiling
                        and self.k % cfg.recluster_every == 0
                        and self.k > 0):
                    self.set_topology(profiling.cluster_devices(
                        self.profiles, cfg.n_edges, seed=cfg.seed + self.k))
            # --- hardware costs ---------------------------------------------
            et = self.profiles.epoch_time(self.rng)          # (N,)
            ee = self.profiles.epoch_energy(self.rng)        # (N,)
            ec = self.comm.ec_time(self.rng)                 # (M,)
            de = self.comm.de_time(self.rng, m)              # (M,)
            if participate is None:
                participate = np.ones(cfg.n_devices, bool)
            t_sgd = np.zeros(m)
            e_edge = np.zeros(m)
            for j in range(m):
                sel = (self.edge_assign == j) & participate
                if sel.any():
                    t_sgd[j] = et[sel].max()
                    e_edge[j] = (ee[sel] * g1[j] * g2[j]).sum()
            t_edge = g2 * (g1 * t_sgd + de) + ec
            t_use = float(t_edge.max())
            e_tot = float(e_edge.sum())
        # --- model update ---------------------------------------------------
        if cfg.mode == "real":
            g1c = np.minimum(g1, cfg.gamma_max)
            g2c = np.minimum(g2, cfg.gamma_max)
            # every device runs each epoch of the round's loops; the
            # masks keep gamma1 * gamma2 of them, and aggregation weighs
            # only the participating devices
            self._round_epochs = (
                hfl.round_epochs(g1c, g2c, self.edge_assign,
                                 cfg.gamma_max, cfg.gamma_max),
                int(np.sum((g1c * g2c)[self.edge_assign][participate])))
            self.epoch_counts["computed"] += self._round_epochs[0]
            self.epoch_counts["useful"] += self._round_epochs[1]
            with TraceAnnotation("hfl.round"):
                part = jnp.asarray(participate)
                sizes = self.fed.device_sizes() * part.astype(jnp.float32)
                self.bank, self.global_model, self.edge_models = \
                    self._cloud_round(
                        self.bank, self.fed.x, self.fed.y, sizes,
                        self._edge_assign_j, jnp.asarray(g1c),
                        jnp.asarray(g2c), self._next_key())
            with TraceAnnotation("hfl.acc"):
                acc = float(self._acc_fn(self.global_model,
                                         self.fed.test_x, self.fed.test_y))
        else:
            acc = self._analytic_update(g1, g2, participate)
        self.acc = acc
        self.total_energy += e_tot
        h_edges = np.stack([t_sgd * g1 * g2, ec, e_edge], axis=1)
        return h_edges.astype(np.float32), t_use, e_tot

    def _analytic_update(self, g1, g2, participate) -> float:
        """Saturating progress + drift/staleness penalties (calibrated to
        real mode; see EXPERIMENTS.md §Calibration)."""
        cfg = self.cfg
        epochs = g1.astype(np.float64) * g2.astype(np.float64)
        w = self._edge_sizes / self._edge_sizes.sum()
        progress = float(np.sum(w * (1.0 - np.exp(-cfg.a_rate * epochs))))
        drift = cfg.drift_coef * float(np.std(epochs)) / max(
            float(np.mean(epochs)), 1.0) * cfg.a_rate
        stale = cfg.stale_coef * cfg.a_rate * float(np.mean(
            np.maximum(g2 - 4, 0)))
        gap = cfg.a_max - self.acc
        noise = self.rng.normal(0, cfg.noise)
        new = self.acc + gap * max(progress - drift - stale, 0.0) + noise
        return float(np.clip(new, 0.05, cfg.a_max))

    # ------------------------------------------------------------------
    def _state(self) -> np.ndarray:
        if self.cfg.mode == "real":
            with TraceAnnotation("hfl.state"):
                return state_mod.build_state(
                    self.pca_state, self.global_model, self.edge_models,
                    self._h_edges, self.k, self.t_re, self.acc,
                    t_threshold=self.cfg.threshold_time)
        # analytic mode: PCA rows replaced by per-edge epoch statistics
        m = self.cfg.n_edges
        s1 = np.zeros((m + 1, self.cfg.n_pca), np.float32)
        s1[0, 0] = self.acc
        s1[1:, 0] = self._h_edges[:, 0] / 100.0
        s1[1:, 1] = self._h_edges[:, 2] / 50.0
        s3 = np.array([[self.k / 50.0,
                        self.t_re / self.cfg.threshold_time,
                        self.acc]], np.float32)
        s2 = self._h_edges / np.array([[100.0, 100.0, 50.0]], np.float32)
        return np.concatenate([s1, np.concatenate([s3, s2], 0)], axis=1)

    def step(self, action: np.ndarray):
        """action: (2M,) raw continuous; projected to γ ∈ [1, γ_max]^2M
        (§3.6 nearest-feasible-solution — with a box feasible set the
        L2-nearest integer point is clip(round(·)))."""
        with TraceAnnotation("hfl.step"):
            return self._step(action)

    def _step(self, action: np.ndarray):
        cfg = self.cfg
        m = cfg.n_edges
        a = np.clip(np.round(np.asarray(action)), 1, cfg.gamma_max)
        g1 = a[:m].astype(np.int64)
        g2 = a[m:].astype(np.int64)
        acc_old = self.acc
        h_edges, t_use, e_tot = self._run_round(g1, g2)
        self.t_re -= t_use
        self.k += 1
        self._h_edges = h_edges
        r = reward_mod.reward(self.acc, acc_old, e_tot, cfg.epsilon)
        done = self.t_re < 0
        self.energy_hist.append(e_tot)
        self.acc_hist.append(self.acc)
        self.time_hist.append(t_use)
        info = {"acc": self.acc, "energy": e_tot, "t_use": t_use,
                "t_re": self.t_re, "g1": g1, "g2": g2,
                "epochs_computed": self._round_epochs[0],
                "epochs_useful": self._round_epochs[1]}
        self._observe_health(info)
        return self._state(), float(r), bool(done), info

    def _observe_health(self, info: dict, *, flushed: bool = True)\
            -> None:
        """Feed the (optional) health monitor and surface any new
        events in ``info["health"]``. Host-side reads only — never a
        state mutation or RNG draw — so health-on vs health-off
        trajectories stay bitwise-identical (tests/test_ledger.py).
        May raise :class:`HealthAbort` when the opt-in abort policy is
        armed and a critical event fires."""
        if self.health is None:
            return
        bank_finite = None
        if (flushed and self.cfg.mode == "real"
                and self.health.cfg.check_bank):
            vec = getattr(self, "_global_vec", None)
            if vec is not None:          # async real: flat global vector
                bank_finite = bool(np.isfinite(np.asarray(vec)).all())
            else:
                bank_finite = all(
                    bool(jnp.isfinite(leaf).all())
                    for leaf in jax.tree.leaves(self.global_model))
        info["health"] = [e.to_dict() for e in self.health.observe(
            step=self.k,
            sim_time=self.cfg.threshold_time - self.t_re,
            acc=self.acc, flushed=flushed, bank_finite=bank_finite)]

    # hooks for baselines --------------------------------------------------
    def set_topology(self, edge_assign: np.ndarray) -> None:
        """Share baseline / re-clustering hook: replace the device->edge
        assignment (the profiling module's periodic re-cluster, §3.1)."""
        self.edge_assign = np.asarray(edge_assign, np.int64)
        self._edge_assign_j = jnp.asarray(self.edge_assign)
        self._edge_sizes = np.array(
            [np.sum(self.edge_assign == j) * self.cfg.n_local
             for j in range(self.cfg.n_edges)], np.float32)

    def run_fixed(self, g1: int, g2: int,
                  participate: Optional[np.ndarray] = None):
        """One round at uniform frequencies (Vanilla-HFL / Favor / etc.)."""
        m = self.cfg.n_edges
        return self.step_raw(np.full(m, g1), np.full(m, g2), participate)

    def step_raw(self, g1: np.ndarray, g2: np.ndarray,
                 participate: Optional[np.ndarray] = None):
        with TraceAnnotation("hfl.step"):
            return self._step_raw(g1, g2, participate)

    def _step_raw(self, g1: np.ndarray, g2: np.ndarray,
                  participate: Optional[np.ndarray] = None):
        acc_old = self.acc
        h_edges, t_use, e_tot = self._run_round(
            np.asarray(g1, np.int64), np.asarray(g2, np.int64), participate)
        self.t_re -= t_use
        self.k += 1
        self._h_edges = h_edges
        r = reward_mod.reward(self.acc, acc_old, e_tot, self.cfg.epsilon)
        self.energy_hist.append(e_tot)
        self.acc_hist.append(self.acc)
        self.time_hist.append(t_use)
        info = {"acc": self.acc, "energy": e_tot, "t_use": t_use,
                "t_re": self.t_re,
                "epochs_computed": self._round_epochs[0],
                "epochs_useful": self._round_epochs[1]}
        self._observe_health(info)
        return self._state(), float(r), bool(self.t_re < 0), info

    @property
    def state_shape(self):
        return (self.cfg.n_edges + 1, self.cfg.n_pca + 3)

    @property
    def action_dim(self):
        return 2 * self.cfg.n_edges


# ---------------------------------------------------------------------------
# event-driven asynchronous mode (repro.runtime; DESIGN.md §Async runtime)
# ---------------------------------------------------------------------------

class AsyncHFLEnv(HFLEnv):
    """Event-driven asynchronous HFL: edges report on their own clocks.

    The synchronous env charges every round ``max_j t_edge_j`` — one
    straggler dominates wall-clock. Here each edge trains continuously:
    it downloads the current global model, runs its (γ1, γ2) round, and
    posts an *upload event* after its simulated per-edge duration
    (``repro.runtime.clock``). The cloud holds uploads in a FedBuff-style
    buffer (``repro.runtime.buffer``) and advances the global model —
    with staleness-decayed weights ``w_j s(τ_j)`` — once ``buffer_k``
    updates are in. With zero decay and ``buffer_k == n_edges`` the
    flush is bitwise the synchronous cloud aggregation.

    One env **step = one upload event**: the action ``(γ1, γ2)``
    programs the *next* round of the edge whose upload was just
    processed, so the agent acts per edge at upload events rather than
    per global round (action_dim == 2). The observation appends six
    columns to the synchronous state: per-edge staleness, in-flight
    status, a deciding-edge one-hot (row 0 carries the buffer fill
    fraction), and — for the fault model — dropped-upload counts,
    pending-retry attempts, and an outage/departed status flag, so the
    DRL agent can learn around faults.

    **Fault tolerance** (``repro.runtime.faults``; DESIGN.md §5): pass
    a :class:`FaultSpec` to inject per-edge upload dropout, transient
    failures with capped-exponential-backoff retries, edge-outage
    windows, and join/leave churn — all as first-class events on the
    same deterministic queue. ``AsyncConfig.flush_deadline`` adds
    graceful degradation: a buffer that cannot reach K in time flushes
    the survivors with coverage-corrected weights (the current global
    vector anchors the missing mass; ``ref.coverage_aggregate_ref``).
    A null/omitted spec reproduces the fault-free runtime **bitwise**.
    Crash recovery: ``repro.checkpoint.store.save_runtime`` /
    ``load_runtime`` snapshot and restore the full runtime state.
    """

    def __init__(self, cfg: EnvConfig, async_cfg=None, faults=None,
                 telemetry=None, health=None):
        from repro.runtime import AsyncConfig
        from repro.telemetry import Telemetry
        super().__init__(cfg, health=health)
        self.acfg = async_cfg or AsyncConfig()
        self.buffer_k = self.acfg.buffer_k or cfg.n_edges
        self.faults = faults
        # explicit facade wins; else EnvConfig.telemetry toggles one on.
        # A disabled facade keeps every hook a no-op and the queue
        # observer None — the telemetry-off code path is unchanged.
        if telemetry is None:
            telemetry = (Telemetry() if cfg.telemetry
                         else Telemetry.disabled())
        self.telemetry = telemetry
        if cfg.mode == "real":
            # with a sharded context the per-edge round compiles under
            # GSPMD with the bank row-sharded, the masked edge
            # aggregation as per-shard kernel launches + psum and a
            # shard-local resync — the full (N, P) bank never lands on
            # one device, and with shard-aligned edges the trajectory
            # is bitwise the single-chip one (tests/test_sharded_bank)
            self._edge_round = hfl.make_edge_round(
                self._loss_fn, cfg.lr, cfg.batch_size, cfg.n_edges,
                cfg.gamma_max, cfg.gamma_max, ctx=self.agg_ctx)

    # ------------------------------------------------------------------
    def reset(self) -> np.ndarray:
        from repro.core import flatbank
        from repro.runtime import EventQueue, FaultInjector, StalenessBuffer
        cfg = self.cfg
        m = cfg.n_edges
        # placeholders: the superclass warmup round builds a state
        # before the async structures exist
        self.buffer = None
        self._deciding = None
        self._in_flight = np.zeros(m, bool)
        self._staleness = np.zeros(m, np.float32)
        # per-episode fault state: its dedicated generator folds the
        # episode index in so PPO episodes see varied fault traces while
        # a fresh env stays bitwise-reproducible run to run
        tm = self.telemetry if self.telemetry.enabled else None
        self._injector = FaultInjector(self.faults, m,
                                       seed_offset=self.episode,
                                       telemetry=tm)
        self._incarnation = np.zeros(m, np.int64)
        self._last_action = [(2, 2)] * m
        super().reset()                 # sync warmup round + PCA fit
        self.version = 0
        self._abase = self._next_key()  # generation keys: fold_in(abase, v)
        if cfg.mode == "real":
            self._spec = flatbank.bank_spec(self.bank)
            self._global_vec = self._spec.flatten_model(self.global_model)
            self._edge_mat = self._spec.flatten(self.edge_models)
            sizes = self.fed.device_sizes()
            self._dev_sizes = sizes
            self._edge_w = np.asarray(jax.ops.segment_sum(
                sizes, self._edge_assign_j, m), np.float32)
        else:
            self._edge_w = self._edge_sizes.copy()
        self.queue = EventQueue()
        self.queue.now = cfg.threshold_time - self.t_re  # after warmup
        # fresh trace per episode; the observer hook is None when
        # telemetry is disabled, so pop/schedule stay untouched
        self.telemetry.begin_episode(self.episode, self.queue.now, m)
        self.queue.observer = tm
        self.buffer = StalenessBuffer(
            self.buffer_k, decay=self.acfg.decay,
            decay_a=self.acfg.decay_a, ctx=self.agg_ctx,
            telemetry=tm, clock=self.queue)
        self.n_flushes = 0
        self._edge_version = np.zeros(m, np.int64)
        self._last_time = self.queue.now
        self._last_flush_time = self.queue.now
        self._last_upload_lost = False
        self._flush_info = None
        # declared faults (outage windows, churn) become first-class
        # events on the same deterministic queue; a null spec schedules
        # nothing, keeping the event trace bitwise-identical
        self._injector.schedule_initial(self.queue)
        g0 = np.full(2, 2, np.int64)    # warmup frequencies (Alg. 1 l.3)
        for j in range(m):
            self._launch_round(j, int(g0[0]), int(g0[1]))
        ev = self._process_upload()     # first upload picks first decider
        if ev is not None:
            self._deciding = ev.edge
        return self._state()

    # ------------------------------------------------------------------
    def _launch_round(self, edge: int, g1: int, g2: int) -> None:
        """Edge downloads the current global model and starts a
        (γ1, γ2) round now; its upload lands after the simulated
        per-edge duration. Departed edges stay dormant until a join
        event relaunches them."""
        from repro.runtime import edge_round_cost
        if not self._injector.alive[edge]:
            return
        self._last_action[edge] = (int(g1), int(g2))
        cost = edge_round_cost(self.profiles, self.comm, self.edge_assign,
                               edge, g1, g2, self.rng)
        snapshot = self._global_vec if self.cfg.mode == "real" else None
        self.queue.schedule(cost.time, edge, kind="upload",
                            g1=g1, g2=g2, cost=cost, version=self.version,
                            snapshot=snapshot,
                            incarnation=int(self._incarnation[edge]))
        self._edge_version[edge] = self.version
        self._in_flight[edge] = True
        self.telemetry.round_launched(edge, self.queue.now, cost,
                                      g1, g2, self.version)

    # ------------------------------------------------------------------
    # fault-event handlers (repro.runtime.faults)
    # ------------------------------------------------------------------
    def _handle_leave(self, j: int) -> None:
        """Mobility churn: edge ``j`` departs. Its in-flight round is
        voided (the incarnation bump makes the pending upload a ghost);
        its bank rows stay bit-identical until it rejoins."""
        fi = self._injector
        if not fi.alive[j]:
            return
        fi.alive[j] = False
        fi.retry_pending[j] = 0
        self._incarnation[j] += 1
        self._in_flight[j] = False
        self.telemetry.churn(j, self.queue.now, "leave")

    def _handle_join(self, j: int) -> None:
        """Mobility churn: edge ``j`` (re)joins. Real mode resyncs only
        the joining edge's bank rows to the current global model
        (``hfl.masked_resync`` — every other row comes back
        bit-identical), then the edge relaunches with its last
        programmed frequencies."""
        fi = self._injector
        if fi.alive[j]:
            return
        fi.alive[j] = True
        self._incarnation[j] += 1
        self.telemetry.churn(j, self.queue.now, "join")
        if self.cfg.mode == "real":
            self._edge_mat = self._edge_mat.at[j].set(
                self._global_vec.astype(self._edge_mat.dtype))
            alive_1h = np.zeros(self.cfg.n_edges, bool)
            alive_1h[j] = True
            mat = hfl.masked_resync(self._edge_mat,
                                    self._spec.flatten(self.bank),
                                    self._edge_assign_j,
                                    jnp.asarray(alive_1h),
                                    ctx=self.agg_ctx)
            self.bank = self._spec.unflatten(mat)
            self.edge_models = self._spec.unflatten(self._edge_mat)
        self._edge_version[j] = self.version
        g1, g2 = self._last_action[j]
        self._launch_round(j, g1, g2)

    def _maybe_deadline_flush(self) -> None:
        """Graceful degradation: if K has not been met within the flush
        deadline, proceed with the survivors (coverage-corrected)."""
        dl = self.acfg.flush_deadline
        if dl > 0 and len(self.buffer) > 0 and not self.buffer.ready \
                and self.queue.now - self._last_flush_time >= dl:
            self._flush(degraded=True)

    def _process_upload(self):
        """Pop events until one upload lands (or is permanently
        dropped): fault events (outage boundaries, churn, retries) are
        handled transparently in between. Realizes the landed upload's
        training, buffers the update, and flushes the cloud when the
        buffer fills (or the flush deadline lapses). Returns ``None``
        iff the queue drained (every edge departed)."""
        cfg = self.cfg
        fi = self._injector
        while True:
            if not len(self.queue):
                return None
            ev = self.queue.pop()
            kind = ev.kind
            if kind == "outage_start":
                fi.in_outage[ev.edge] = True
                self.telemetry.outage(ev.edge, ev.time, started=True)
            elif kind == "outage_end":
                fi.in_outage[ev.edge] = False
                self.telemetry.outage(ev.edge, ev.time, started=False)
            elif kind == "leave":
                self._handle_leave(ev.edge)
            elif kind == "join":
                self._handle_join(ev.edge)
            else:                                   # an upload attempt
                pay = ev.payload
                if pay.get("incarnation", 0) \
                        != int(self._incarnation[ev.edge]):
                    self.telemetry.ghost_upload(ev.edge, ev.time)
                    continue    # ghost: the edge departed mid-round
                attempt = pay.get("attempt", 0)
                first = pay.get("first_try", ev.time)
                fate = fi.upload_fate(ev.edge, attempt, ev.time, first)
                if fate == "retry":
                    fi.retry_pending[ev.edge] = attempt + 1
                    # capped exponential backoff + a fresh comm-model
                    # upload draw prices the retry
                    delay = fi.retry_delay(self.comm, ev.edge, attempt)
                    self.telemetry.retry_scheduled(ev.edge, ev.time,
                                                   attempt, delay)
                    self.queue.schedule(
                        delay, ev.edge, kind="upload",
                        **{**pay, "attempt": attempt + 1,
                           "first_try": first})
                    self._maybe_deadline_flush()
                    continue
                fi.retry_pending[ev.edge] = 0
                break
            self._maybe_deadline_flush()
        j, pay, cost = ev.edge, ev.payload, ev.payload["cost"]
        lost = fate == "drop"
        self._in_flight[j] = False
        if lost:
            self.telemetry.upload_dropped(j, ev.time, attempt)
        else:
            self.telemetry.upload_landed(
                j, ev.time, pay["version"],
                self.version - pay["version"], attempt)
        if lost:
            # the round's compute (and energy) is spent, but the update
            # never reaches the cloud: nothing is buffered and in real
            # mode the edge round is not realized (the device state was
            # lost mid-round; its bank rows keep their previous values)
            pass
        elif cfg.mode == "real":
            key = jax.random.fold_in(self._abase, pay["version"])
            self.bank, edge_vec = self._edge_round(
                self.bank, self.fed.x, self.fed.y, self._dev_sizes,
                self._edge_assign_j, jnp.int32(j), jnp.int32(pay["g1"]),
                jnp.int32(pay["g2"]), pay["snapshot"], key)
            self._edge_mat = self._edge_mat.at[j].set(
                edge_vec.astype(self._edge_mat.dtype))
            self.edge_models = self._spec.unflatten(self._edge_mat)
            self.buffer.push(j, edge_vec, float(self._edge_w[j]),
                             pay["version"])
        else:
            self.buffer.push(j, None, float(self._edge_w[j]),
                             pay["version"],
                             epochs=pay["g1"] * pay["g2"], g2=pay["g2"])
        self.total_energy += cost.energy
        self._h_edges[j] = np.float32(
            [cost.t_sgd * pay["g1"] * pay["g2"], cost.ec, cost.energy])
        self._flushed = False
        if self.buffer.ready:
            self._flush()
        else:
            self._maybe_deadline_flush()
        self._staleness = np.float32(self.version - self._edge_version)
        dt = self.queue.now - self._last_time
        self._last_time = self.queue.now
        self.t_re = cfg.threshold_time - self.queue.now
        self.energy_hist.append(cost.energy)
        self.acc_hist.append(self.acc)
        self.time_hist.append(dt)
        self._last_upload_lost = lost
        return ev

    def _flush(self, degraded: bool = False) -> None:
        """Cloud aggregation of the buffered updates (staleness-decayed
        weights); bumps the model version and re-measures accuracy.

        ``degraded=True`` is the deadline path: K was not met, so the
        survivors aggregate with coverage-corrected weights — in real
        mode the current global vector anchors the missing data mass
        (``ref.coverage_aggregate_ref``); the analytic model's coverage
        factor already damps partial flushes."""
        cfg = self.cfg
        anchor, m_w = None, 0.0
        if degraded and cfg.mode == "real":
            missing = max(self.buffer_k - len(self.buffer), 0)
            anchor = self._global_vec
            m_w = float(missing * np.mean(self._edge_w))
        flush_version = self.version
        glob, info = self.buffer.flush(self.version,
                                       self.acfg.max_staleness,
                                       anchor=anchor, anchor_weight=m_w)
        info["degraded"] = degraded
        self._flush_info = info
        applied = False
        if cfg.mode == "real":
            if glob is not None:
                self._global_vec = glob
                self.global_model = self._spec.unflatten_model(glob)
                self.acc = float(self._acc_fn(
                    self.global_model, self.fed.test_x, self.fed.test_y))
                applied = True
        elif info["edges"]:
            self.acc = self._analytic_flush(info)
            applied = True
        if applied:
            self.version += 1
            self.n_flushes += 1
            self.k += 1
        self._flushed = applied
        # reset the deadline clock even for a vacuous flush (every slot
        # staleness-dropped) — otherwise it would re-trigger every event
        self._last_flush_time = self.queue.now
        self.telemetry.flush_event(self.queue.now, flush_version, info,
                                   applied, degraded)

    def _analytic_flush(self, info) -> float:
        """Analytic-mode accuracy update per flush — the synchronous
        saturating-progress model transplanted to buffered aggregation
        (EXPERIMENTS.md §Calibration, async notes):

        * each buffered update contributes its per-epoch progress with
          the *buffer-normalized* staleness weight q_j = w_j s(τ_j) /
          Σ w s — mirroring the real flush, where the decay folds into
          the weight vector of a normalized mean (a stale update loses
          influence, it does not shrink the step);
        * a partial buffer only represents Σ_b w_j / W of the data, so
          progress scales by coverage^cov_pow (K = M fresh reduces
          exactly to the synchronous update);
        * staleness adds to the γ2 penalty via the mean buffer τ.
        """
        cfg = self.cfg
        slots = info["meta"]
        epochs = np.float64([s["epochs"] for s in slots])
        p = 1.0 - np.exp(-cfg.a_rate * epochs)
        q = np.float64(info["weights"])
        q = q / max(q.sum(), 1e-12)                  # within-buffer norm
        coverage = float(sum(self._edge_sizes[j]
                             for j in set(info["edges"]))
                         / self._edge_sizes.sum())
        info["coverage"] = coverage
        progress = float(np.sum(q * p)) * coverage ** cfg.cov_pow
        drift = cfg.drift_coef * float(np.std(epochs)) / max(
            float(np.mean(epochs)), 1.0) * cfg.a_rate
        g2s = np.float64([s["g2"] for s in slots])
        stale = cfg.stale_coef * cfg.a_rate * (
            float(np.mean(np.maximum(g2s - 4, 0)))
            + float(np.mean(info["staleness"])))
        gap = cfg.a_max - self.acc
        noise = self.rng.normal(0, cfg.noise)
        new = self.acc + gap * max(progress - drift - stale, 0.0) + noise
        return float(np.clip(new, 0.05, cfg.a_max))

    # ------------------------------------------------------------------
    def step(self, action: np.ndarray):
        """action: (2,) raw continuous (γ1, γ2) for the deciding edge's
        next round (same nearest-feasible projection as the synchronous
        env). Advances the simulation by exactly one upload event."""
        cfg = self.cfg
        a = np.clip(np.round(np.asarray(action).reshape(-1)[:2]), 1,
                    cfg.gamma_max).astype(np.int64)
        acc_old = self.acc
        if self._deciding is not None:
            self._launch_round(self._deciding, int(a[0]), int(a[1]))
        ev = self._process_upload()
        if ev is None:
            # the queue drained: every edge departed (mobility churn)
            # and nothing can ever arrive again — terminal state
            self._deciding = None
            self.telemetry.fleet_down(self.queue.now)
            info = {"acc": self.acc, "energy": 0.0, "t_use": 0.0,
                    "t_re": self.t_re, "edge": -1, "g1": 0, "g2": 0,
                    "flushed": False, "version": self.version,
                    "staleness": self._staleness.copy(),
                    "fleet_down": True, "dropped": False}
            self._observe_health(info, flushed=False)
            if self.telemetry.enabled:
                info["telemetry"] = self.telemetry.metrics.brief()
            return self._state(), 0.0, True, info
        self._deciding = ev.edge
        cost = ev.payload["cost"]
        r = reward_mod.reward(self.acc, acc_old, cost.energy, cfg.epsilon)
        done = self.t_re < 0
        info = {"acc": self.acc, "energy": cost.energy,
                "t_use": self.time_hist[-1], "t_re": self.t_re,
                "edge": ev.edge, "g1": ev.payload["g1"],
                "g2": ev.payload["g2"], "flushed": self._flushed,
                "version": self.version,
                "staleness": self._staleness.copy(),
                "dropped": self._last_upload_lost,
                "retries": int(ev.payload.get("attempt", 0))}
        self._observe_health(info, flushed=self._flushed)
        if self.telemetry.enabled:
            info["telemetry"] = self.telemetry.metrics.brief()
        return self._state(), float(r), bool(done), info

    # ------------------------------------------------------------------
    def _state(self) -> np.ndarray:
        base = super()._state()                      # (M+1, n_pca+3)
        m = self.cfg.n_edges
        extra = np.zeros((m + 1, 6), np.float32)
        if self.buffer is not None:
            extra[0, 0] = len(self.buffer) / max(self.buffer_k, 1)
        extra[1:, 0] = self._staleness / 10.0
        extra[1:, 1] = self._in_flight.astype(np.float32)
        if self._deciding is not None:
            extra[1 + self._deciding, 2] = 1.0
        fi = getattr(self, "_injector", None)
        if fi is not None:
            # fault columns: cumulative dropped uploads, pending retry
            # attempt, and outage/departed status (0.5 = outage,
            # 1 = departed); row 0 carries fleet totals
            extra[1:, 3] = fi.n_dropped / 10.0
            extra[1:, 4] = np.minimum(fi.retry_pending, 10) / 10.0
            extra[1:, 5] = np.where(~fi.alive, 1.0,
                                    np.where(fi.in_outage, 0.5, 0.0))
            extra[0, 3] = float(fi.n_dropped.sum()) / 10.0
            extra[0, 4] = float(fi.n_retries.sum()) / 10.0
            extra[0, 5] = float((~fi.alive).sum()) / max(m, 1)
        return np.concatenate([base, extra], axis=1)

    @property
    def state_shape(self):
        return (self.cfg.n_edges + 1, self.cfg.n_pca + 9)

    @property
    def action_dim(self):
        return 2
