"""`PopulationEngine`: B independent federations as one vmapped scan.

PR 2/4 made an entire federation a pure function of its SoA `FleetState`
driven by `lax.scan`; a population of B federations is therefore just one
more batch axis.  This engine builds B real `DeviceScaleEngine`s from
member specs (so data, partitions, cluster assignments, and malicious
masks come from the exact standalone construction code), stacks their
states and padded tables along a leading population axis, and `jax.vmap`s
the *unmodified* fused round + in-jit controller + Eqn-12 queue over it —
`run_scanned(K)` executes all B federations in a single device program and
unstacks per-member `FLTrace`s bit-identical to standalone
``Federation.from_spec(spec).run_scanned(K)`` runs.

Member heterogeneity splits into three classes:

build-time   fields only read at construction (seed, data params,
             malicious_frac, dt_max_dev, channel p_good, fault subsets):
             realized per member by the standalone constructors, stacked.
lifted       scalar knobs read inside the round (lr, iota, pkt_fail, DP
             sigma, alpha0/alpha_growth, fault intensities, Lyapunov
             budget/penalty, the trust-vs-fedavg flag): lifted into traced
             per-member arrays and rebound through a `_MemberView` —
             a duck-typed `self` whose spec fields hold tracers.
static       everything that changes the compiled program (shapes,
             component kinds, fault gates `may_*`, corrupt_mode, DP
             on/off, calibrate_dt): must be uniform; checked at build.

Ragged per-member widths (padded membership M, partition width W) pad to
the population-wide maximum — bitwise-neutral, since fill-gathers never
read padded columns and masked reductions only append zeros.

The population axis shards over a 1-D mesh (`ShardingSpec`, axis "pop"):
members are independent, so the program partitions with zero collectives —
one host serves ``device_count`` times the population at the same
wall-clock.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.api.components import WeightedAggregator
from repro.api.engine import DeviceScaleEngine, scan_step
from repro.api.records import FLTrace, RoundRecord
from repro.api.spec import FederationSpec
from repro.control import policy as ctl_policy
from repro.control import queue as ctl_queue
from repro.faults.model import FaultModel

from .spec import POP_AXIS, PopulationSpec

__all__ = ["PopulationEngine", "PopulationMember"]


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise ValueError(f"population: {msg}")


def _uniform(specs, label: str, get):
    vals = [get(s) for s in specs]
    _require(all(v == vals[0] for v in vals),
             f"{label} must be uniform across the population (it is "
             f"compiled static); got {vals}")
    return vals[0]


class _MemberView(DeviceScaleEngine):
    """A duck-typed `DeviceScaleEngine` carrying one member's vmap-sliced
    leaves and lifted spec scalars.  Only the attributes the fused round /
    controller features read are set; the round methods themselves are
    inherited unmodified — the population runs the exact standalone
    device math."""

    def __init__(self, **attrs):          # noqa: D401 — attribute bag
        for k, v in attrs.items():
            setattr(self, k, v)


class _FaultView(FaultModel):
    """`FaultModel` over lifted per-member fault scalars.  The static
    ``may_*`` gates come from the (uniform) base spec so the compiled
    program is member-independent; the probabilities/scales the jnp
    methods read are tracers."""

    def __init__(self, base: FaultModel, p: Dict[str, Any]):
        self._base = base.spec
        self.n = base.n
        self.corrupt_dev = p["corrupt_dev"]
        self.poison_dev = p["poison_dev"]
        self._seed = p.get("seed", base._seed)
        self.spec = dataclasses.replace(
            base.spec, dropout=p["dropout"],
            straggler_frac=p["straggler_frac"],
            straggler_factor=p["straggler_factor"],
            twin_spike_prob=p["twin_spike_prob"],
            twin_spike_scale=p["twin_spike_scale"],
            corrupt_scale=p["corrupt_scale"],
            poison_scale=p["poison_scale"])

    active = property(lambda self: self._base.active)
    may_drop = property(lambda self: self._base.may_drop)
    may_straggle = property(lambda self: self._base.may_straggle)
    may_spike = property(lambda self: self._base.may_spike)
    may_corrupt = property(lambda self: self._base.may_corrupt)
    may_poison = property(lambda self: self._base.may_poison)


class _LiftedWeightedAggregator(WeightedAggregator):
    """Trust/fedavg selected by a traced per-member flag: both weight
    vectors are computed and `jnp.where`-selected, so the selected lane is
    bitwise-identical to the corresponding standalone branch."""

    def __init__(self, use_kernel: bool, uniform_flag):
        super().__init__(uniform=False, use_kernel=use_kernel)
        self._flag = uniform_flag         # () bool tracer: True = fedavg

    def _effective_weights(self, weights, mask):
        m = mask.astype(weights.dtype)
        uni = m / jnp.maximum(jnp.sum(m), 1.0)
        return jnp.where(self._flag, uni, weights)


# lifted FederationSpec scalars: (mp key, getter)
_LIFTED_SPEC = (
    ("lr", lambda s: s.lr),
    ("iota", lambda s: s.iota),
    ("pkt_fail", lambda s: s.channel.pkt_fail),
    ("noise", lambda s: s.privacy.noise),
    ("alpha0", lambda s: s.clustering.alpha0),
    ("alpha_growth", lambda s: s.clustering.alpha_growth),
)
_LIFTED_FAULT = ("dropout", "straggler_frac", "straggler_factor",
                 "twin_spike_prob", "twin_spike_scale", "corrupt_scale",
                 "poison_scale")


class PopulationEngine:
    """B federations, one device program (see module docstring)."""

    def __init__(self, specs: Sequence[FederationSpec], *,
                 sharding=None, pop_axis: str = POP_AXIS,
                 federations: Optional[Sequence[Any]] = None):
        from repro.api.federation import Federation
        self.specs = [s for s in specs]
        self.B = len(self.specs)
        _require(self.B >= 1, "need at least one member spec")
        if federations is None:
            federations = [Federation.from_spec(s, controller=c)
                           for s, c in zip(self.specs,
                                           self._build_controllers())]
        self.federations = list(federations)
        engines = [f.engine for f in self.federations]
        self._check_static(engines)
        e0 = engines[0]
        self._proto = e0
        self.task = e0.task
        self.n_devices = int(e0.spec.fleet.n_devices)
        self.n_clusters = int(e0.spec.clustering.n_clusters)

        # --- stack member state + tables (padded to population-wide M/W)
        stack = lambda xs: jnp.stack(list(xs))                 # noqa: E731
        self.state = jax.tree.map(lambda *ls: jnp.stack(ls),
                                  *[e.state for e in engines])
        self._scan_times = stack(e._scan_times for e in engines)
        M = max(e.tables.member_table.shape[1] for e in engines)
        W = max(e.tables.part_idx.shape[1] for e in engines)
        n = self.n_devices

        def padded(t):
            pad = lambda a, w, fill: jnp.pad(                  # noqa: E731
                a, ((0, 0), (0, w - a.shape[1])), constant_values=fill)
            return t._replace(part_idx=pad(t.part_idx, W, 0),
                              member_table=pad(t.member_table, M, n),
                              member_mask=pad(t.member_mask, M, False))

        # the members' fleet tables, stacked: the scan's argument
        self._tables = jax.tree.map(lambda *ls: jnp.stack(ls),
                                    *[padded(e.tables) for e in engines])
        mp: Dict[str, Any] = {
            "trans": stack(e._trans for e in engines),
            "per_slot": jnp.asarray(
                [ctl_queue.per_slot_of(f.controller)
                 for f in self.federations], jnp.float32),
        }
        for key, get in _LIFTED_SPEC:
            mp[key] = jnp.asarray([float(get(s)) for s in self.specs],
                                  jnp.float32)
        if e0.faults.active:
            flt = {k: jnp.asarray(
                [float(getattr(s.faults, k)) for s in self.specs],
                jnp.float32) for k in _LIFTED_FAULT}
            flt["corrupt_dev"] = stack(e.faults.corrupt_dev
                                       for e in engines)
            flt["poison_dev"] = stack(e.faults.poison_dev for e in engines)
            seeds = [int(s.faults.seed) for s in self.specs]
            if any(sd != seeds[0] for sd in seeds):
                # poison patterns derive from the seed with host-side
                # integer arithmetic — they cannot trace (checked below)
                flt["seed"] = jnp.asarray(seeds, jnp.int32)
            mp["flt"] = flt
        agg_kinds = {s.aggregator.kind for s in self.specs}
        self._lift_agg = agg_kinds == {"trust", "fedavg"}
        if self._lift_agg:
            mp["agg_uniform"] = jnp.asarray(
                [s.aggregator.kind == "fedavg" for s in self.specs], bool)
        self._pol_step, self._pol_needs_obs, pol_mp = self._build_policy()
        if pol_mp:
            mp["pol"] = pol_mp
        self._mp = mp

        # --- optional population-axis placement
        self.mesh: Optional[Mesh] = None
        self.pop_axis = pop_axis
        if sharding is not None and getattr(sharding, "is_sharded", False):
            _require(len(sharding.mesh) == 1,
                     "the population shards over a 1-D mesh (one pop axis)")
            shards = int(sharding.mesh[0])
            _require(self.B % shards == 0,
                     f"mesh has {shards} shards, which does not divide the "
                     f"population size {self.B}")
            if sharding.axes:
                self.pop_axis = sharding.axes[0]
            from repro.api.placement import _mesh_devices
            self.mesh = Mesh(_mesh_devices((shards,)), (self.pop_axis,))
            sh = NamedSharding(self.mesh, PartitionSpec(self.pop_axis))
            put = lambda t: jax.tree.map(                      # noqa: E731
                lambda l: jax.device_put(l, sh), t)
            self.state = put(self.state)
            self._scan_times = jax.device_put(self._scan_times, sh)
            self._mp = put(self._mp)
            self._tables = put(self._tables)

        self._rounds = [0] * self.B
        self._energy_used = [0.0] * self.B      # exact f64, per member
        self._sinks: List[Any] = [None] * self.B
        self._retain = [True] * self.B
        self._scan_cache: Dict[int, Any] = {}

    # ------------------------------------------------------------------ #
    @classmethod
    def from_population(cls, pspec: PopulationSpec) -> "PopulationEngine":
        return cls(pspec.expand(), sharding=pspec.sharding,
                   pop_axis=pspec.pop_axis())

    def _build_controllers(self):
        """Member controllers from the registries; identical DQN pretrains
        are built once and shared (the agent is immutable at deploy time —
        fixed/lyapunov controllers carry per-member queue state and are
        always built per member)."""
        from repro.api import registry
        cache: Dict[str, Any] = {}
        out = []
        for s in self.specs:
            factory = registry.CONTROLLERS.get(s.controller.kind)
            if s.controller.kind == "dqn":
                key = json.dumps(s.controller.params, sort_keys=True,
                                 default=repr)
                if key not in cache:
                    cache[key] = factory(s.controller.params)
                out.append(cache[key])
            else:
                out.append(factory(s.controller.params))
        return out

    # ------------------------------------------------------------------ #
    def _check_static(self, engines) -> None:
        specs = self.specs
        for e in engines:
            _require(type(e) is DeviceScaleEngine,
                     f"member engines must be unsharded device-scale "
                     f"engines; got {type(e).__name__}")
            _require(e._padded, "members need a mask-aware aggregator "
                     "(run_scanned's padded fused round)")
        _uniform(specs, "fleet.n_devices", lambda s: s.fleet.n_devices)
        _uniform(specs, "clustering.n_clusters",
                 lambda s: s.clustering.n_clusters)
        _uniform(specs, "local_batch", lambda s: s.local_batch)
        _uniform(specs, "task", lambda s: (s.task.kind,
                                           sorted(s.task.params.items())))
        _uniform(specs, "controller.kind", lambda s: s.controller.kind)
        _uniform(specs, "fleet.calibrate_dt",
                 lambda s: s.fleet.calibrate_dt)
        _uniform(specs, "privacy.clip", lambda s: s.privacy.clip)
        _uniform(specs, "aggregator.use_kernel",
                 lambda s: s.aggregator.use_kernel)
        agg_kinds = {s.aggregator.kind for s in specs}
        if len(agg_kinds) > 1:
            _require(agg_kinds == {"trust", "fedavg"},
                     f"mixed aggregator kinds {sorted(agg_kinds)} — only "
                     "the trust/fedavg pair lifts to a traced flag")
            _require(specs[0].privacy.clip <= 0.0,
                     "mixed trust/fedavg aggregators cannot combine with "
                     "DP (the DP weight path branches on the kind)")
        else:
            _uniform(specs, "aggregator.params",
                     lambda s: sorted(s.aggregator.params.items()))
        for gate in ("may_drop", "may_straggle", "may_spike",
                     "may_corrupt", "may_poison"):
            _uniform(specs, f"faults.{gate}",
                     lambda s, g=gate: getattr(s.faults, g))
        if specs[0].faults.may_corrupt:
            _uniform(specs, "faults.corrupt_mode",
                     lambda s: s.faults.corrupt_mode)
        if specs[0].faults.may_poison:
            _uniform(specs, "faults.seed (with poisoning on: the poison "
                     "patterns derive from it statically)",
                     lambda s: s.faults.seed)
        _require(len({e._n_actions for e in engines}) == 1,
                 "controller n_actions must be uniform")
        _require(len({e._fused_global for e in engines}) == 1,
                 "aggregator fused-global support must be uniform")

    # ------------------------------------------------------------------ #
    def _build_policy(self):
        """The population scan policy: per-member scalar knobs lifted into
        ``mp["pol"]``, identical math to `repro.control.policy`."""
        ctls = [f.controller for f in self.federations]
        kind = self.specs[0].controller.kind
        pols = [c.scan_policy() for c in ctls]
        if kind == "fixed":
            pol_mp = {"a": jnp.asarray([int(c.a) for c in ctls],
                                       jnp.int32)}

            def step(state, obs, p):
                return p["a"], state
            return step, False, pol_mp
        if kind == "lyapunov":
            pol_mp = {k: jnp.asarray([float(getattr(c, k)) for c in ctls],
                                     jnp.float32)
                      for k in ("kappa", "f_star", "v0", "v_growth")}
            n_actions = int(ctls[0].n_actions)

            def step(state, obs, p):
                s = ctl_policy.lyapunov_scores(
                    obs.queue, obs.round, obs.cluster_loss, obs.mean_freq,
                    obs.channel_good_frac, n_actions=n_actions,
                    kappa=p["kappa"], f_star=p["f_star"], v0=p["v0"],
                    v_growth=p["v_growth"])
                return jnp.argmax(s).astype(jnp.int32) + 1, state
            return step, False, pol_mp
        # generic (dqn, custom): one shared step closure, per-member carry
        # stacked — requires the step function to be member-independent
        # (all builtin dqn policies are: the net rides in the carry)
        base = pols[0]

        def step(state, obs, p):
            return base.step(state, obs)
        return step, base.needs_obs, None

    def _ctl_state(self):
        """The stacked policy carry, re-fetched from the member controllers
        each segment — exactly as the standalone `run_scanned` re-fetches
        ``scan_policy().state`` per call."""
        states = [f.controller.scan_policy().state
                  for f in self.federations]
        if not jax.tree_util.tree_leaves(states[0]):
            return states[0]
        ctl = jax.tree.map(lambda *ls: jnp.stack(ls), *states)
        if self.mesh is not None:
            sh = NamedSharding(self.mesh, PartitionSpec(self.pop_axis))
            ctl = jax.tree.map(lambda l: jax.device_put(l, sh), ctl)
        return ctl

    # ------------------------------------------------------------------ #
    def _member_view(self, mp: Dict[str, Any], tables) -> _MemberView:
        """Bind one member's vmap-sliced leaves (its fleet ``tables``) +
        lifted scalars to a duck-typed engine the inherited round methods
        run against."""
        e0 = self._proto
        s0 = e0.spec
        spec = dataclasses.replace(
            s0,
            lr=mp["lr"], iota=mp["iota"],
            clustering=dataclasses.replace(
                s0.clustering, alpha0=mp["alpha0"],
                alpha_growth=mp["alpha_growth"]),
            channel=dataclasses.replace(s0.channel,
                                        pkt_fail=mp["pkt_fail"]),
            privacy=dataclasses.replace(s0.privacy, noise=mp["noise"]))
        faults = (_FaultView(e0.faults, mp["flt"])
                  if e0.faults.active else e0.faults)
        aggregator = (_LiftedWeightedAggregator(
            s0.aggregator.use_kernel, mp["agg_uniform"])
            if self._lift_agg else e0.aggregator)
        return _MemberView(
            spec=spec, task=e0.task, faults=faults, aggregator=aggregator,
            _sentinel=e0._sentinel, _n_actions=e0._n_actions,
            _padded=True, _fused_global=e0._fused_global, tables=tables,
            _trans=mp["trans"], _queue_per_slot=mp["per_slot"])

    def _build_scan_fn(self, K: int):
        pol_step = self._pol_step
        needs_obs = self._pol_needs_obs

        def member_body(state, times, ctl, energy, mp, tables):
            view = self._member_view(mp, tables)
            return scan_step(
                (state, times, ctl, energy), view._scan_features,
                view._scan_round,
                lambda ctl, cobs: pol_step(ctl, cobs, mp.get("pol")),
                needs_obs)

        vbody = jax.vmap(member_body, in_axes=(0, 0, 0, 0, 0, 0))
        mp = self._mp

        def run_k(state, times, ctl, energy, tables):
            def body(carry, _):
                state, times, ctl, energy = carry
                return vbody(state, times, ctl, energy, mp, tables)
            return jax.lax.scan(body, (state, times, ctl, energy), None,
                                length=K)

        jit_kw = dict(donate_argnums=(0,))
        if self.mesh is not None:
            pop = NamedSharding(self.mesh, PartitionSpec(self.pop_axis))
            carry_sh = (jax.tree.map(lambda _: pop, self.state), pop,
                        jax.tree.map(lambda _: pop, self._ctl_state()),
                        pop)
            ys_sh = {k: NamedSharding(self.mesh,
                                      PartitionSpec(None, self.pop_axis))
                     for k in ("t", "cluster", "a", "dur", "consumed",
                               "loss")}
            jit_kw.update(in_shardings=carry_sh + (
                jax.tree.map(lambda _: pop, self._tables),),
                out_shardings=(carry_sh, ys_sh))
        return jax.jit(run_k, **jit_kw)

    # ------------------------------------------------------------------ #
    def set_member_sink(self, b: int, sink, *, retain: bool = True) -> None:
        """Attach a per-member trace sink (e.g. a run-dir `JsonlSink`)."""
        self._sinks[b] = sink
        self._retain[b] = retain

    def run_scanned(self, K: int, *,
                    eval_final: bool = True) -> List[FLTrace]:
        """Run K rounds of every member in one scan; per-member traces.

        Consecutive calls continue (times/energy/round counters carry), so
        segment sequences match one long run — the invariant the pool
        supervisor checkpoints on, inherited from the standalone engine."""
        K = int(K)
        energy0 = jnp.asarray([np.float32(e) for e in self._energy_used],
                              jnp.float32)
        if self.mesh is not None:
            energy0 = jax.device_put(energy0, NamedSharding(
                self.mesh, PartitionSpec(self.pop_axis)))
        args = (self.state, self._scan_times, self._ctl_state(), energy0,
                self._tables)
        fn = self._scan_cache.get(K)
        if fn is None:
            fn = self._build_scan_fn(K)
            self._scan_cache[K] = fn
        (state, times, _, _), ys = fn(*args)
        self.state = state
        self._scan_times = times
        return self._emit(ys, K, eval_final)

    def _emit(self, ys, K: int, eval_final: bool) -> List[FLTrace]:
        ys = jax.device_get(ys)             # leaves (K, B); one host sync
        queue_host = None
        traces = []
        for b in range(self.B):
            base = self._rounds[b]
            self._rounds[b] += K
            # per-member exact-f64 energy: the same sequential additions
            # the standalone `_emit_scanned_trace` performs
            cum = []
            for ci in np.asarray(ys["consumed"][:, b], np.float32):
                self._energy_used[b] += float(ci)
                cum.append(self._energy_used[b])
            sync_queue = getattr(self.federations[b].controller,
                                 "sync_queue", None)
            if sync_queue is not None:
                if queue_host is None:
                    queue_host = jax.device_get(self.state.queue)
                sync_queue(queue_host[b])
            trace = FLTrace(records=[], sink=self._sinks[b],
                            retain=self._retain[b])
            for i in range(K):
                trace.append(RoundRecord(
                    t=float(ys["t"][i, b]), round=base + i + 1,
                    cluster=int(ys["cluster"][i, b]),
                    a=int(ys["a"][i, b]), loss=float(ys["loss"][i, b]),
                    acc=None, energy=cum[i], agg_count=base + i + 1))
            if eval_final:
                params_b = jax.tree.map(lambda l: l[b],
                                        self.state.global_params)
                ev = self.task.evaluate(params_b,
                                        self.federations[b].engine.data)
                trace.append(RoundRecord(
                    t=float(ys["t"][-1, b]) + float(ys["dur"][-1, b]),
                    round=self._rounds[b],
                    cluster=int(ys["cluster"][-1, b]),
                    a=int(ys["a"][-1, b]), loss=ev["loss"],
                    acc=ev.get("acc"), energy=self._energy_used[b],
                    agg_count=self._rounds[b]))
            traces.append(trace)
        return traces

    # ------------------------------------------------------------------ #
    # per-member serve surface (checkpoint/resume interop with repro.serve)
    # ------------------------------------------------------------------ #
    def member(self, b: int) -> "PopulationMember":
        return PopulationMember(self, int(b))

    def member_rounds(self, b: int) -> int:
        return self._rounds[b]

    def member_energy(self, b: int) -> float:
        return self._energy_used[b]

    def _member_resumable(self, b: int) -> dict:
        fleet = jax.tree.map(lambda l: l[b], self.state)
        return {"fleet": fleet, "times": self._scan_times[b]}

    def _restore_member(self, b: int, tree: dict, *, rounds: int,
                        energy: float) -> None:
        fleet = tree["fleet"]
        self.state = jax.tree.map(
            lambda L, l: L.at[b].set(jnp.asarray(l)), self.state, fleet)
        self._scan_times = self._scan_times.at[b].set(
            jnp.asarray(tree["times"], jnp.float32))
        self._rounds[b] = int(rounds)
        self._energy_used[b] = float(energy)


class _MemberEngineView:
    """The engine half of a `PopulationMember`: exposes exactly the
    resumable surface `repro.serve.runner` drives, backed by slices of the
    stacked population state — so member checkpoints are byte-compatible
    with single-tenant `repro.serve` run dirs."""

    def __init__(self, pop: PopulationEngine, b: int):
        self._pop = pop
        self.b = b

    @property
    def spec(self):
        return self._pop.specs[self.b]

    @property
    def round(self) -> int:
        return self._pop.member_rounds(self.b)

    @property
    def energy_used(self) -> float:
        return self._pop.member_energy(self.b)

    def resumable_state(self) -> dict:
        return self._pop._member_resumable(self.b)

    def restore_resumable(self, tree: dict, *, rounds: int,
                          energy: float) -> None:
        self._pop._restore_member(self.b, tree, rounds=rounds,
                                  energy=energy)


class PopulationMember:
    """A federation-shaped facade over one population slot — what
    `repro.serve.runner.save_resumable`/`restore_resumable` consume."""

    def __init__(self, pop: PopulationEngine, b: int):
        self.engine = _MemberEngineView(pop, b)
        self.controller = pop.federations[b].controller
        self.spec = pop.specs[b]
