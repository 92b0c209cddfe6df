"""Execution engines behind the `Federation` facade.

The engine contract is explicit: the `Engine` protocol below (a
``from_spec`` classmethod taking the spec plus built component instances,
``run``, ``run_scanned``, one `FLTrace`/`RoundRecord` schema), and engines
register under `repro.api.registry.ENGINES` keyed by ``spec.scale`` —
`Federation` resolves the scale like any other component.

*Where* an engine runs is spec data too: `DeviceScaleEngine` resolves
``spec.sharding`` through `repro.api.placement` and, when a mesh is
present, commits the initial `FleetState` to its leaf-group shardings and
pins jit ``in_shardings``/``out_shardings`` on both the per-event fused
round and the whole ``run_scanned`` scan (device leaves over the fleet
axis, cluster stack + event times over the cluster axis, scalars/global
model replicated).  The single-device default builds exactly the
pre-placement jits.

`DeviceScaleEngine` is the paper's §IV-D discrete-event simulator rebuilt
around an immutable **`FleetState`** struct-of-arrays pytree: twins,
reputation, channel, stacked per-cluster parameters, energy, the global
model, and the RNG key all live in one donated device-resident structure.
Each asynchronous cluster round — batch gather from a precomputed padded
partition matrix, vmapped local training, the Eqn 4-5 belief/reputation
update, Eqn-6 aggregation through the masked Pallas ``trust_aggregate``
kernel, the optional DP path, energy accounting (Eqns 7-8), the twin
observe/calibrate step, and the Eqn-19 staleness-weighted global average —
is **one fused jit-compiled call** `_fleet_round(state, c, a, members,
mask)`.  The deployment's read-only tables (data, padded partition,
membership, attacker masks) are one `FleetTables` pytree that every round
program takes as an argument, not as captured constants (`_reading`).
Only the event heap, the controller's `select`, evaluation, and
the float64 cumulative-energy tally stay on the host: a single 4-scalar
metrics dict (bounded a, round duration, consumed energy, mean loss)
crosses the device boundary per round.

Ragged cluster memberships run as fixed-shape grids: mask-aware
aggregators (``supports_mask=True``, i.e. trust/fedavg) share one compiled
round over a (n_clusters, M) padded membership table whose padding slots
hold an out-of-range sentinel (gathers fill, scatters drop).  Aggregators
built on rank statistics (krum, median, ...) cannot ignore padded rows, so
the engine compiles one exact-shape round per distinct cluster size
instead — same function, shape-specialized by jit's cache.

The control plane is device-resident too (`repro.control`): the Eqn-12
Lyapunov deficit queue lives in `FleetState` as an array leaf advanced
in-jit with the realized consumption, and every built-in controller exposes
a scannable `(state, CtlObs) -> (action, state)` policy.  ``run_scanned(K)``
lowers K whole rounds — cluster scheduling by argmin over a carried
per-cluster event-time vector (reproducing the heap's (t, c) order),
in-jit `select`, the fused round, and the queue update — into a **single
`lax.scan`**; per-round metrics are stacked on device and synced once at
the end, where the float64 cumulative-energy tally is rebuilt from the
stacked f32 consumptions by the same sequential f64 additions the event
loop performs (device f64 is unavailable with x64 disabled, and this is
bitwise identical to accumulating a f64 leaf in the scan carry).  One
accumulation does differ: the scan carries per-cluster event times in f32
where the heap sums f64 Python floats, so two clusters whose next-event
times fall within f32 rounding of each other could in principle be popped
in a different order — at the tested seeds and scales the traces match
bit-for-bit on scheduling and counters, but sub-ulp event-time ties are
not ordered identically by construction.  The
event-heap path remains for ragged schedules (``sim_seconds`` cutoffs,
per-round evaluation) and exact-shape robust aggregators.

``fused=False`` runs the *identical* round function eagerly (op-by-op
dispatch with per-round host syncs) — the pre-refactor execution profile.
Fused and reference modes consume the same RNG streams and the same
fixed-shape math, so their traces match at a fixed seed — bit for bit on
scheduling, counters and accuracies; to the last ulp on float reductions,
where XLA's fused (FMA-contracted) form may differ from eager dispatch
(tests/test_api.py::test_fused_round_parity_with_reference).
One *statistical* change from the pre-refactor engine: batches are always
sampled with replacement (`sample_member_batch`'s fixed-shape randint);
the old per-member loop sampled without replacement when a shard held at
least ``local_batch`` examples.

The legacy `AsyncFederation` entry point is a shim over this engine, so
both entry points produce identical traces at a fixed seed
(tests/test_api.py::test_spec_parity_with_legacy covers the shim's
config-translation path).

`DatacenterEngine` drives the sharded `fl_step` mode-A/B train steps under
the same controller protocol and emits the same `RoundRecord` trace.
"""
from __future__ import annotations

import copy
import heapq
from typing import (Any, NamedTuple, Optional, Protocol, runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np

from repro.control import policy as ctl_policy
from repro.control import queue as ctl_queue
from repro.core.clustering import (cluster_devices, ensure_nonempty,
                                   padded_membership, tolerance_bound)
from repro.core.energy import channel_transition, round_energy, step_channel
from repro.core.envs import OBS_DIM
from repro.core.trust import (belief, gradient_diversity, learning_quality,
                              staleness_weights, time_weighted_average,
                              trust_weights, update_reputation)
from repro.core.twin import (MEMBER_FILLS, calibrate, calibrated_freq,
                             init_twins, observe_round_members,
                             sample_deviation, TwinState)
from repro.data.federated import padded_partition, sample_member_batch
from repro.faults import FaultModel
from repro.kernels.ops import flatten_rows
from repro.kernels.trust_aggregate import padded_width
from repro.obs import annotate, compiles

from . import placement as placement_lib
from .components import ControllerCtx
from .records import FLTrace, RoundRecord
from .registry import register_engine
from .spec import (DATACENTER_SCALE, DEVICE_SCALE, FederationSpec,
                   SHARD_MAP_IMPL)

# the jit-sharded GSPMD path stays registry-selectable under its own scale
# (`DeviceScaleEngine.from_spec` also routes back to it via
# ``ShardingSpec.impl='gspmd'``)
GSPMD_DEVICE_SCALE = "device-gspmd"


def _flat_members(new, row, width=None):
    """The round's member matrix, built once.

    Returns ``flat_new = flatten_rows(new, width)`` and the members'
    (M, N) updates: ``flat_new`` less ``row``, the cluster model local SGD
    started from (no member dim), flattened to the same width, cut to the
    N parameter columns.  One row is subtracted from every member, so no
    (M, N) copy of the cluster model is made, and the Eqns 4-5 statistics
    reduce over the parameters alone.  ``width`` defaults to N; the fused
    round passes the trust kernel's `padded_width`, at which the kernel
    reads ``flat_new`` (zeros past N) with no pad of its own.  The cut
    comes after the subtraction: cut first, XLA builds a second, unpadded
    copy of the matrix for it."""
    flat_new = flatten_rows(new, width)
    flat_row = flatten_rows(jax.tree.map(lambda l: l[None], row), width)
    n = sum(l.size for l in jax.tree.leaves(row))
    return flat_new, (flat_new - flat_row)[:, :n]


def _read_by_id(leaf, fill, members, mask):
    """The members' rows of a fleet-axis ``leaf`` in original device order:
    a gather by id in which the padding sentinel (and so a dropped member)
    reads ``fill``."""
    del mask
    return leaf.at[members].get(mode="fill", fill_value=fill)


class _RoundKeys(NamedTuple):
    """One round's RNG streams; ``flt`` is None without a fault model."""
    key: Any            # the state's key for the next round
    batch: Any
    energy: Any
    channel: Any
    dp: Any
    flt: Any


def scan_step(carry, features, round_fn, policy, needs_obs: bool):
    """One round of a scanned run: the step every fleet engine scans.

    ``carry`` is ``(state, times, ctl, energy, *extra)``.  The cluster
    with the earliest event time runs: ``features(state, c, extra,
    needs_obs)`` gives the controller features and the DQN observation
    (None when not computed: the policy then reads zeros), ``policy(ctl,
    CtlObs)`` picks the raw action, and ``round_fn(state, c, a_raw,
    extra)`` runs the round, returning ``(state, extra, metrics)``."""
    state, times, ctl, energy, *extra = carry
    # the event heap pops min (t, c); argmin breaks ties on the first
    # (lowest) cluster index exactly as tuple order does
    c = jnp.argmin(times).astype(jnp.int32)
    t = times[c]
    with jax.named_scope("fl.control"):
        feats, obs48 = features(state, c, extra, needs_obs)
        if obs48 is None:
            obs48 = jnp.zeros((OBS_DIM,), jnp.float32)
        cobs = ctl_policy.CtlObs(
            round=state.round, cluster=c, queue=state.queue,
            cluster_loss=feats["cluster_loss"],
            cluster_freq=feats["cluster_freq"],
            mean_freq=feats["mean_freq"],
            channel_good_frac=feats["channel_good_frac"],
            energy_used=energy, dqn_obs=obs48)
        a_raw, ctl = policy(ctl, cobs)
    state, extra, m = round_fn(state, c, a_raw, extra)
    times = times.at[c].set(t + m["dur"])
    energy = energy + m["consumed"]
    ys = {"t": t, "cluster": c, "a": m["a"], "dur": m["dur"],
          "consumed": m["consumed"], "loss": m["loss"]}
    return (state, times, ctl, energy, *extra), ys


@runtime_checkable
class Engine(Protocol):
    """The execution-engine contract behind `Federation`.

    An engine registers under `repro.api.registry.ENGINES` keyed by
    ``FederationSpec.scale`` and provides:

      from_spec   classmethod constructor taking the spec plus built
                  component instances (``controller``/``aggregator``/
                  ``task``) and the optional ``data``/``parts``/``fused``
                  overrides; engines that generate their own data ignore
                  the overrides they don't consume.
      run         the engine's native loop; emits the `FLTrace` /
                  `RoundRecord` schema shared by every scale.
      run_scanned exactly-K-rounds lowering with end-of-run metrics sync;
                  engines without one raise ValueError with a pointer to
                  ``run``.

    `Federation` resolves ``spec.scale`` through the registry and calls
    only this surface — adding a scale is a registration, not a facade
    edit.
    """

    spec: FederationSpec

    @classmethod
    def from_spec(cls, spec: FederationSpec, *, controller, aggregator,
                  task, data=None, parts=None,
                  fused: Optional[bool] = None) -> "Engine":
        ...

    def run(self, eval_every: float = 1.0,
            max_rounds: Optional[int] = None) -> FLTrace:
        ...

    def run_scanned(self, K: int, *, eval_final: bool = True) -> FLTrace:
        ...


class FleetState(NamedTuple):
    """Struct-of-arrays state of the whole federation, one jit-donatable
    pytree.  Leaves are device arrays; the only host-side state the engine
    keeps beside this is the event heap, the round counter mirror, and the
    float64 cumulative-energy accumulator (per-device energies live in
    ``twins.energy``)."""
    twins: TwinState            # per-device digital twins (SoA over fleet)
    rep: jnp.ndarray            # (n,)  Eqn-5 reputations
    channel: jnp.ndarray        # (n,)  Markov channel state, int32
    cluster_params: Any         # pytree, leaves (n_clusters, ...)
    global_params: Any          # pytree, leaves (...): Eqn-19 aggregate
    cluster_ts: jnp.ndarray     # (n_clusters,) last-update round, f32
    queue: jnp.ndarray          # ()  Eqn-12 Lyapunov deficit backlog, f32
    round: jnp.ndarray          # ()  global round counter, int32
    key: jnp.ndarray            # typed PRNG key (jax.random.key) driving
                                # every round's randomness; repro.checkpoint
                                # round-trips it via its __key__: marker


class FleetTables(NamedTuple):
    """The deployment's read-only tables, built once at engine init and
    held on the device.  Every round program takes them as a (non-donated)
    argument, never as baked-in constants, so the data live on the device
    once and two fleets of the same shapes share one compiled program.
    They are not state: `FleetState`, `resumable_state` and checkpoints
    leave them out (a resumed engine rebuilds them from the spec)."""
    x: jnp.ndarray              # (n_samples, dim) features
    y: jnp.ndarray              # (n_samples,) labels
    part_idx: jnp.ndarray       # (n, W) int32 padded shard rows
    part_len: jnp.ndarray       # (n,) int32 shard sizes
    member_table: jnp.ndarray   # (n_clusters, M) int32, sentinel n pads
    member_mask: jnp.ndarray    # (n_clusters, M) bool
    malicious: jnp.ndarray      # (n,) f32 label-flip attackers
    misbehaving: jnp.ndarray    # (n,) f32 attackers + Byzantine subsets


class DeviceScaleEngine:
    """Discrete-event asynchronous clustered FL over a device fleet."""

    def __init__(self, spec: FederationSpec, data, parts, *,
                 controller, aggregator, task,
                 fused: Optional[bool] = None, assign=None):
        assert spec.scale in (DEVICE_SCALE, GSPMD_DEVICE_SCALE)
        self.spec = spec
        self.data = data
        self.parts = parts
        self.controller = controller
        self.aggregator = aggregator
        self.task = task
        # where the fleet lives: a jax.sharding mesh resolved from the
        # spec, or the single-device fallback (shardings all None).  This
        # engine is the jit-sharded GSPMD path, so the placement validates
        # under that impl's (stricter, divisible) rules even when the spec
        # resolves to shard_map by default.
        self.placement = placement_lib.resolve(
            spec.sharding, n_devices=spec.fleet.n_devices,
            n_clusters=spec.clustering.n_clusters, impl="gspmd")

        n = spec.fleet.n_devices
        C = spec.clustering.n_clusters
        # typed key (not the legacy raw uint32 pair): same threefry bits,
        # but the dtype survives a checkpoint round-trip as a key
        key = jax.random.key(spec.seed)
        key0, kt, kd, kc, kp, km = jax.random.split(key, 6)
        twins = sample_deviation(kd, init_twins(kt, n), spec.fleet.dt_max_dev)
        sizes = jnp.asarray([len(p) for p in parts], jnp.float32)
        twins = twins._replace(data_size=sizes)
        if assign is None:
            # kc is always split so an assignment override (a caller that
            # skips the O(n*C) k-means) leaves every other stream in the
            # engine untouched
            assign, _ = cluster_devices(kc, twins, C)
        self.assign = ensure_nonempty(np.asarray(assign), C)
        member_table, member_mask = padded_membership(self.assign, C)

        self.malicious = np.zeros(n, bool)
        n_mal = int(spec.fleet.malicious_frac * n)
        if n_mal:
            self.malicious[np.asarray(jax.random.choice(
                km, n, (n_mal,), replace=False))] = True
        malicious = jnp.asarray(self.malicious, jnp.float32)

        # declarative fault injection (spec.faults -> pure-jnp round
        # transforms); the default spec is inert and the gating below is
        # *static*, so fault-free runs compile the exact pre-fault round
        self.faults = FaultModel(spec.faults, n)
        self._sentinel = jnp.int32(n)   # padded-membership fill index
        # the Eqn-4 interaction tallies treat the fault model's static
        # Byzantine subsets exactly like the label-flip attackers: each
        # round a misbehaving member's beta count grows, so reputation —
        # not just the per-round FoolsGold signals — learns persistent
        # attackers (inert spec: both subsets are zero, nothing changes)
        misbehaving = jnp.maximum(
            malicious,
            jnp.maximum(self.faults.corrupt_dev, self.faults.poison_dev))
        part_idx, part_len = padded_partition(parts)
        self.tables = self.placement.replicate(FleetTables(
            x=jnp.asarray(data.x), y=jnp.asarray(data.y),
            part_idx=part_idx, part_len=part_len,
            member_table=member_table, member_mask=member_mask,
            malicious=malicious, misbehaving=misbehaving))

        gp = task.init(kp, dim=data.x.shape[1])
        cparams = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (C,) + l.shape) + 0.0, gp)
        self.state = self.placement.shard_state(FleetState(
            twins=twins, rep=jnp.ones((n,)),
            channel=jnp.zeros((n,), jnp.int32),
            cluster_params=cparams, global_params=gp,
            cluster_ts=jnp.zeros((C,), jnp.float32),
            queue=ctl_queue.init_leaf(),
            round=jnp.zeros((), jnp.int32), key=key0))
        # Eqn-12 replenishment rate of the controller's deficit queue
        # (+inf for budgetless controllers: the queue leaf stays 0)
        self._queue_per_slot = ctl_queue.per_slot_of(controller)

        self._trans = channel_transition(spec.channel.p_good)
        self._n_actions = int(getattr(controller, "n_actions", 10))
        self._needs_ctx = bool(getattr(controller, "needs_ctx", True))
        # mask-aware aggregators share one padded fixed-shape compilation;
        # rank-statistic rules get exact member shapes (one compile per size)
        self._padded = bool(getattr(aggregator, "supports_mask", False))
        if self._padded:
            self._members = [member_table[c] for c in range(C)]
            self._masks = [member_mask[c] for c in range(C)]
        else:
            self._members = [jnp.asarray(np.where(self.assign == c)[0],
                                         jnp.int32) for c in range(C)]
            self._masks = [jnp.ones((len(g),), bool) for g in self._members]

        # aggregators exposing the fused Eqn-6+19 kernel path
        # (`aggregate_flat_with_global`) fold the global average into the same
        # pass when the round is padded and DP is off
        self._fused_global = self._padded and bool(
            getattr(aggregator, "supports_fused_global", False))

        self.fused = True if fused is None else bool(fused)
        # donate the FleetState buffers so the round updates in place
        jit_kw = dict(donate_argnums=(0,))
        if self.placement.is_sharded:
            # pin the round's output placement so the FleetState carry keeps
            # its leaf-group shardings instead of drifting to whatever the
            # SPMD partitioner last inferred; the 4 metrics scalars replicate
            repl = self.placement.replicated()
            jit_kw["out_shardings"] = (
                self.placement.state_shardings(self.state),
                {"a": repl, "dur": repl, "consumed": repl, "loss": repl})
        self._round_fn = (jax.jit(self._round, **jit_kw) if self.fused
                          else self._round)
        self._rounds = 0
        # cumulative energy accumulates host-side in float64 (the per-round
        # `consumed` scalar crosses to the host anyway); a float32 device
        # accumulator would drop sub-ulp additions on long simulations
        self._energy_used = 0.0
        # sink-less scanned segments defer that sync: per-segment consumed
        # stacks queue device-side in `_pending` and the f32 tally carries
        # in `_energy_dev` until something host-visible (a trace, the
        # energy_used property, a checkpoint) flushes them
        self._pending = []
        self._energy_dev = jnp.float32(0.0)
        # per-cluster event times carried *across* run_scanned calls, so
        # run_scanned(K) twice continues exactly where run_scanned(2K)
        # would be — the invariant the checkpointed service mode
        # (`repro.serve`) resumes on.  The round counter and energy tally
        # already carried; this makes the schedule carry too.
        self._scan_times = jnp.zeros((C,), jnp.float32)
        # optional streaming tap for emitted traces (`repro.serve` points
        # this at a JSONL file); None = the in-memory batch default
        self.trace_sink = None
        self.trace_retain = True
        # optional telemetry bundle (`repro.obs.EngineObs`): metrics
        # registry + span recorder.  Attached via `set_obs`; everything it
        # feeds on either already crosses the host boundary (the stacked
        # per-segment metrics, the event loop's per-round dict) or is a
        # separate read-only jitted reduction — never a change to the
        # round program, so traces stay bit-identical with it attached
        self.obs = None
        self._obs_summary_fn = None
        compiles.install()              # counts every compile, obs or not
        # control plane: jitted host ctx features / observation builders
        # + compiled scan paths
        self._features_fn = jax.jit(
            lambda state, tables, c: self._reading(tables)._ctl_features(
                state, c))
        self._obs_fn = jax.jit(self._obs_of)
        self._scan_cache = {}       # K -> compiled lax.scan-over-rounds

    # ------------------------------------------------------------------ #
    @classmethod
    def from_spec(cls, spec: FederationSpec, *, controller, aggregator,
                  task, data=None, parts=None,
                  fused: Optional[bool] = None,
                  assign=None) -> "DeviceScaleEngine":
        if data is None or parts is None:
            data, parts = default_device_data(spec)
        # 1-D meshes default to the cluster-major shard_map engine (the
        # membership-local path); impl='gspmd' or the 'device-gspmd' scale
        # keeps the jit-sharded fallback.  `cls is` so the subclasses
        # (gspmd pin, cluster-major itself) never re-dispatch.
        if (cls is DeviceScaleEngine and spec.sharding.is_sharded
                and spec.sharding.resolved_impl() == SHARD_MAP_IMPL):
            from .cluster_engine import ClusterMajorEngine
            return ClusterMajorEngine(
                spec, data, parts, controller=controller,
                aggregator=aggregator, task=task, fused=fused,
                assign=assign)
        return cls(spec, data, parts, controller=controller,
                   aggregator=aggregator, task=task, fused=fused,
                   assign=assign)

    # ------------------------------------------------------------------ #
    # streamed traces + resumable state (the `repro.serve` surface)
    # ------------------------------------------------------------------ #
    def set_trace_sink(self, sink, *, retain: bool = True) -> None:
        """Stream every emitted `RoundRecord` to ``sink`` (an object with
        ``append(RoundRecord)``, e.g. `repro.api.records.JsonlSink`).
        ``retain=False`` stops the trace from also accumulating records in
        memory — required for unbounded service runs."""
        self.trace_sink = sink
        self.trace_retain = bool(retain)

    def _new_trace(self) -> FLTrace:
        return FLTrace(sink=self.trace_sink, retain=self.trace_retain)

    # telemetry (`repro.obs` — see API.md "Observability") -------------- #
    def set_obs(self, obs) -> None:
        """Attach an `repro.obs.EngineObs` telemetry bundle (``None``
        detaches).  The engine publishes per-segment round aggregates,
        state summaries, compile events, and fault tallies into it.
        Attaching telemetry never alters the compiled round program —
        emitted traces stay bit-identical to an uninstrumented run
        (pinned by tests/test_obs.py)."""
        self.obs = obs
        if obs is not None:
            obs.publish_static(self)

    def _obs_span(self, name: str, fence_on=None, **attrs):
        """The span ``name`` of the attached recorder, or with none
        attached the profiler annotation ``fl.<name>`` alone."""
        if self.obs is None:
            return annotate("fl." + name)
        return self.obs.span(name, fence_on=fence_on, **attrs)

    def _instrument_compile(self, name: str, fn, args):
        """Compile ``fn`` for ``args`` under telemetry.

        With no obs attached, returns ``fn`` unchanged (the plain jit
        path — compilation happens implicitly on first call, exactly as
        before).  Under telemetry, trace+lower+compile explicitly (AOT
        builds the *same* executable the first jit call would) inside a
        ``span("compile")``, and feed the optimized HLO through
        `hlo_stats.analyze_module` and the bytes of the constants the
        trace captured (``fl_program_const_bytes``) into the one-time
        compile event."""
        if self.obs is None:
            return fn
        with self.obs.span("compile", fn=name) as sp:
            traced = fn.trace(*args)
            compiled = traced.lower().compile()
        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = None
        self.obs.record_compile(name, sp.dur_s, hlo,
                                const_bytes=compiles.const_bytes(
                                    traced.jaxpr))
        return compiled

    def _compile_scan(self, K: int, pol, args, name: str):
        """Build the K-round scan (under telemetry compiled ahead of time)
        and cache it."""
        fn = self._instrument_compile(
            f"{name}[K={K}]", self._build_scan_fn(K, pol), args)
        self._scan_cache[K] = fn
        return fn

    def _dispatch_scan(self, fn, args, K: int):
        """``fn(*args)`` inside the ``round`` span.  Under telemetry the
        span fences on the result: `mark` stamps the async-dispatch time,
        the fence charges the span for the device compute it queued."""
        with self._obs_span("round", mode="scanned", rounds=K) as sp:
            out = fn(*args)
            if self.obs is not None:
                sp.mark("dispatch")
                jax.block_until_ready(out)
        return out

    def obs_state_summary(self) -> dict:
        """Host scalars for the telemetry gauges: Eqn-12 deficit-queue
        level, Eqn-4 trust-weight (reputation) summary stats, and the
        fleet's total β (negative-interaction) tally.  One read-only
        jitted reduction over `FleetState` — never part of the round
        program, so sampling it cannot perturb compiled math."""
        if self._obs_summary_fn is None:
            def summarize(state):
                rep = state.rep
                return {"queue_deficit": state.queue,
                        "reputation_min": rep.min(),
                        "reputation_mean": rep.mean(),
                        "reputation_max": rep.max(),
                        "twin_beta_sum": state.twins.beta.sum()}
            self._obs_summary_fn = jax.jit(summarize)
        out = jax.device_get(self._obs_summary_fn(self.state))
        return {k: float(v) for k, v in out.items()}

    @property
    def scan_times(self) -> jnp.ndarray:
        """The carried per-cluster next-event times of the scanned path."""
        return self._scan_times

    def resumable_state(self) -> dict:
        """Everything device-resident a resumed run needs, as one
        checkpointable pytree: the full `FleetState` (including the RNG-key
        leaf and the Eqn-12 queue) plus the carried per-cluster event
        times.  Host-side scalars (round counter, f64 energy tally) ride in
        the checkpoint manifest instead — f64 would not survive an f32
        npz/jnp round-trip with x64 disabled."""
        self._flush_pending()           # manifest energy must be exact
        return {"fleet": self.state, "times": self._scan_times}

    def restore_resumable(self, tree: dict, *, rounds: int,
                          energy: float) -> None:
        """Adopt a `resumable_state` pytree (typically loaded through
        `repro.checkpoint`) plus the manifest scalars.  The engine must
        have been built from the same spec (assignments, partitions and the
        malicious mask are all deterministic in the spec seed, so a fresh
        process reconstructs them bit-identically)."""
        self.state = self.placement.shard_state(tree["fleet"])
        self._scan_times = jnp.asarray(tree["times"], jnp.float32)
        self._rounds = int(rounds)
        self._energy_used = float(energy)
        self._pending = []
        self._energy_dev = jnp.float32(energy)
        sync_queue = getattr(self.controller, "sync_queue", None)
        if sync_queue is not None:      # host controller adopts the
            sync_queue(self.state.queue)  # restored Eqn-12 backlog

    # ------------------------------------------------------------------ #
    # the fused round: everything below runs inside one jit call
    # ------------------------------------------------------------------ #
    def _reading(self, tables: FleetTables) -> "DeviceScaleEngine":
        """This engine reading ``tables`` in place of its own.  Each round
        program traces the round methods on such a view of its ``tables``
        argument, so the fleet's data enter the program as an argument and
        never as a constant."""
        view = copy.copy(self)
        view.tables = tables
        return view

    def _round(self, state: FleetState, tables: FleetTables, c, a_raw,
               members, mask):
        """`_fleet_round` over the ``tables`` argument (the per-event jit)."""
        return self._reading(tables)._fleet_round(state, c, a_raw, members,
                                                  mask)

    def _obs_of(self, state: FleetState, tables: FleetTables, c):
        view = self._reading(tables)
        return view._scan_obs(state, c, view._ctl_features(state, c))

    def _cluster_freq_table(self, twins) -> jnp.ndarray:
        """Straggler (min) calibrated frequency of every cluster, (C,).
        One masked reduction over the padded membership table per call —
        the old engine recomputed the full-fleet `calibrated_freq` O(C^2)
        times per frequency pick."""
        tb = self.tables
        f = calibrated_freq(twins)
        fmat = f.at[tb.member_table].get(mode="fill", fill_value=jnp.inf)
        fmin = jnp.min(jnp.where(tb.member_mask, fmat, jnp.inf), axis=1)
        return jnp.where(tb.member_mask.any(axis=1), fmin, 1.0)

    def _fleet_round(self, state: FleetState, c, a_raw, members, mask):
        """One asynchronous cluster round (paper §IV-D), state -> state.

        Fuses: Alg.-2 tolerance bound, padded batch gather, vmapped local
        SGD, Eqns 4-5 trust, Eqn-6 aggregation (masked Pallas kernel),
        optional DP, Eqns 7-8 energy, twin observe/calibrate, channel step,
        and the Eqn-19 global aggregate.  ``members``/``mask`` are a
        fixed-shape member slice (padded with the sentinel n, or exact).
        The member phase runs in the helpers below, which the cluster-major
        engine calls too; this method adds the scatters into the fleet and
        Eqn 19."""
        spec = self.spec
        fm = self.faults
        twins = state.twins
        with jax.named_scope("fl.batch"):
            keys = self._round_keys(state.key)
            members, mask, mask_f, cnt, empty = self._member_mask(
                keys.flt, members, mask)
            a = self._bounded_action(a_raw, self._cluster_freq_table(twins),
                                     c, state.round)

        # with a fused-global aggregator the Eqn-6 aggregate never leaves
        # the kernel (see the Eqn-19 block below); DP needs the bare
        # aggregate to clip against, so it keeps the two-step path
        fuse_global = self._fused_global and spec.privacy.clip <= 0.0
        # the kernel's width (member rows + the cluster stack's rows): it
        # then streams flat_new with no pad of its own
        stack = jax.tree.leaves(state.cluster_params)
        width = (padded_width(members.shape[0] + stack[0].shape[0],
                              sum(l[0].size for l in stack))
                 if fuse_global else None)
        new, batch, cur, flat_new, rep_m, w = self._member_update(
            state, c, members, mask, a, keys, _read_by_id, width)
        with jax.named_scope("fl.trust"):
            rep = state.rep.at[members].set(rep_m, mode="drop")
        if not fuse_global:
            agg = self._cluster_aggregate(new, w, mask, cur, keys.dp, mask_f,
                                          cnt)
            with jax.named_scope("fl.aggregate"):
                cparams = jax.tree.map(
                    lambda L, g: L.at[c].set(g.astype(L.dtype)),
                    state.cluster_params, agg)

        losses, e = self._member_cost(state, new, batch, a, members, mask,
                                      mask_f, keys.energy, _read_by_id)
        with jax.named_scope("fl.twins"):
            consumed = jnp.sum(e)
            twins = observe_round_members(twins, members, losses, e,
                                          self.tables.misbehaving)
            if spec.fleet.calibrate_dt:
                twins = calibrate(twins)
            channel = step_channel(keys.channel, state.channel, self._trans)

        with jax.named_scope("fl.aggregate"):
            # --- Eqn 19: staleness-weighted global aggregate (async pull)
            rnd = state.round + 1
            ts = state.cluster_ts.at[c].set(rnd.astype(jnp.float32))
            if fuse_global:
                # one kernel pass: Eqn-6 reduction of the member updates +
                # substitution into the cluster stack + the Eqn-19 average
                # ((n_clusters + C, block) tiles per grid step; the per-shard
                # unit under a mesh placement)
                gparams = self.aggregator.aggregate_flat_with_global(
                    flat_new, w, mask, state.cluster_params,
                    staleness_weights(rnd.astype(jnp.float32) - ts), c)
                cparams = state.cluster_params
            else:
                gparams, _ = time_weighted_average(
                    cparams, rnd.astype(jnp.float32) - ts)
            cparams = jax.tree.map(
                lambda L, g: L.at[c].set(g.astype(L.dtype)), cparams,
                gparams)

        with jax.named_scope("fl.twins"):
            if fm.may_drop:
                # graceful degradation: a fully-dropped cluster spends nothing
                # and leaves every model/trust/twin leaf untouched — only the
                # RNG key, channel, and round counter advance, so the scheduler
                # re-enqueues the cluster instead of writing a zeroed aggregate
                revert = lambda old, newv: jax.tree.map(
                    lambda o, v: jnp.where(empty, o, v), old, newv)
                consumed = jnp.where(empty, 0.0, consumed)
                twins = revert(state.twins, twins)
                rep = revert(state.rep, rep)
                cparams = revert(state.cluster_params, cparams)
                gparams = revert(state.global_params, gparams)
                ts = revert(state.cluster_ts, ts)

            # --- Eqn 12: the deficit queue advances in-jit with the realized
            # consumption (budgetless controllers have per_slot=inf -> q = 0)
            queue = ctl_queue.advance(state.queue, consumed,
                                      self._queue_per_slot)

            # --- round duration from the *post-calibration* straggler freq
            dur = a.astype(jnp.float32) / jnp.maximum(
                self._cluster_freq_table(twins)[c], 1e-6)
            if fm.may_straggle:
                dur = fm.straggle(keys.flt, dur, mask, members)

        new_state = FleetState(
            twins=twins, rep=rep, channel=channel, cluster_params=cparams,
            global_params=gparams, cluster_ts=ts, queue=queue, round=rnd,
            key=keys.key)
        metrics = {"a": a, "dur": dur, "consumed": consumed,
                   "loss": jnp.sum(losses * mask_f) / cnt}
        return new_state, metrics

    # ------------------------------------------------------------------ #
    # the member phase: one copy for every fleet engine.  ``read(leaf,
    # fill, members, mask)`` reads the members' rows of a fleet-axis
    # leaf — by original id here (`_read_by_id`), from the cluster's slot
    # block in the cluster-major engine — and is the only thing in which
    # the engines' member math differs.
    # ------------------------------------------------------------------ #
    def _round_keys(self, key) -> "_RoundKeys":
        """The round's RNG streams.  An active fault model splits one extra
        key; inert specs keep the exact pre-fault stream (and compile the
        exact pre-fault program — every ``fm.may_*`` gate is a static
        Python bool)."""
        if self.faults.active:
            return _RoundKeys(*jax.random.split(key, 6))
        return _RoundKeys(*jax.random.split(key, 5), None)

    def _member_mask(self, kflt, members, mask):
        """The round's member slots after the fault model's dropout.

        Dropped members leave the padded mask AND become the padding
        sentinel, so every downstream read fills neutrally and every
        scatter (reputation, twin observe) drops them — the round treats a
        dropped device exactly like a padding slot.  Returns members, mask,
        the mask as f32, the member count (at least 1) and ``empty``: a
        fully-dropped cluster skips its event (the degenerate all-padding
        aggregate would zero the cluster row); None with dropout off."""
        fm = self.faults
        if fm.may_drop:
            mask = fm.drop_mask(kflt, mask, members)
            members = jnp.where(mask, members, self._sentinel)
        mask_f = mask.astype(jnp.float32)
        cnt = jnp.maximum(jnp.sum(mask_f), 1.0)
        empty = jnp.sum(mask_f) < 0.5 if fm.may_drop else None
        return members, mask, mask_f, cnt, empty

    def _bounded_action(self, a_raw, freq, c, rnd):
        """The controller's choice capped by the Alg.-2 tolerance bound;
        ``freq`` is the straggler frequency of every cluster.

        T_m is the fastest cluster's time for the *requested* local phase
        (a_req / f_max, the convention test_tolerance_bound_caps_slow_
        clusters pins); slower clusters get proportionally fewer steps,
        scaling in as alpha grows.  The old reference (one step of the
        fastest cluster) made the cap floor to 1 for every cluster at
        alpha <= 1, silencing every frequency controller."""
        cl = self.spec.clustering
        a_req = jnp.clip(jnp.asarray(a_raw), 1, self._n_actions)
        t_ref = a_req.astype(jnp.float32) / jnp.maximum(jnp.max(freq), 1e-6)
        alpha = jnp.minimum(
            1.0, cl.alpha0 + cl.alpha_growth * rnd.astype(jnp.float32))
        a = tolerance_bound(a_req, freq[c], t_ref, alpha)
        return jnp.clip(a, 1, self._n_actions)

    def _member_update(self, state: FleetState, row, members, mask, a, keys,
                       read, width):
        """Local batches, ``a`` local SGD steps on every member from the
        cluster model (row ``row`` of the stack), the flat member matrix at
        ``width``, and the Eqns 4-5 trust update.

        Returns ``(new, batch, cur, flat_new, rep_m, w)``: the members'
        models, their batches, the cluster model they started from, the
        flat member matrix, the members' new reputations and their Eqn-6
        trust weights."""
        spec = self.spec
        task = self.task
        fm = self.faults
        tb = self.tables
        with jax.named_scope("fl.batch"):
            # --- local batches from the padded partition matrix
            with jax.named_scope("fl.gather"):
                sel = sample_member_batch(keys.batch, tb.part_idx,
                                          tb.part_len, members,
                                          spec.local_batch)
                x = tb.x[sel]
                y = tb.y[sel]
            if fm.may_poison:
                # poisons the sampled features before they enter local_train;
                # for reconstruction tasks (corrupt_labels a no-op) this is
                # the only attack surface that touches the loss
                x = fm.poison_inputs(keys.flt, x, members)
            mal_m = tb.malicious.at[members].get(mode="fill", fill_value=0.0)
            y = jnp.where(mal_m[:, None] > 0.5, task.corrupt_labels(y), y)
            batch = {"x": x, "y": y}

        with jax.named_scope("fl.local_sgd"):
            # --- a local steps on every member (vmap), from the cluster model
            m_dim = members.shape[0]
            cur = jax.tree.map(lambda l: l[row], state.cluster_params)
            stacked = jax.tree.map(
                lambda l: jnp.broadcast_to(l, (m_dim,) + l.shape), cur)
            new = task.local_train(stacked, batch, spec.lr, a)
            if fm.may_corrupt:
                # Byzantine members replace their honest deltas *before* the
                # trust chain sees them — Eqns 4-5 must earn their keep
                new = fm.corrupt_updates(keys.flt, new, stacked, members)

        with jax.named_scope("fl.flatten"):
            flat_new, upd_flat = _flat_members(new, cur, width)
        with jax.named_scope("fl.trust"):
            q = learning_quality(upd_flat, mask)
            div = gradient_diversity(upd_flat, mask)
            tw_m = TwinState(*[read(l, fill, members, mask) for l, fill
                               in zip(state.twins, MEMBER_FILLS)])
            if fm.may_spike:
                # amplified f̂ deviation feeds straight into Eqn 4's
                # 1/(1+|Δf̂|) normalization
                tw_m = fm.spike_twins(keys.flt, tw_m, mask, members)
            b = belief(tw_m, q, spec.channel.pkt_fail, div)
            rep_m = update_reputation(read(state.rep, 1.0, members, mask), b,
                                      spec.channel.pkt_fail, spec.iota)
            w = trust_weights(rep_m, mask)
        return new, batch, cur, flat_new, rep_m, w

    def _cluster_aggregate(self, new, w, mask, cur, kdp, mask_f, cnt):
        """Eqn 6 over the members' models, or its DP form (clipped updates
        from ``cur``, plus noise) when the spec sets a clip.  Mask-aware
        aggregators read the padded member rows with ``mask``; the
        rank-statistic rules get exact member shapes."""
        spec = self.spec
        with jax.named_scope("fl.aggregate"):
            if spec.privacy.clip > 0.0:
                from repro.core.privacy import dp_aggregate
                return dp_aggregate(
                    kdp, new, cur,
                    w if spec.aggregator.kind == "trust" else mask_f / cnt,
                    spec.privacy.clip, spec.privacy.noise, n_clients=cnt)
            return (self.aggregator(new, w, mask) if self._padded
                    else self.aggregator(new, w))

    def _member_cost(self, state: FleetState, new, batch, a, members, mask,
                     mask_f, ke, read):
        """The members' losses on their batches and their Eqns 7-8 round
        energies (zero at padding and dropped slots)."""
        with jax.named_scope("fl.twins"):
            losses = self.task.losses(new, batch)
            true_freq = read(state.twins.freq + state.twins.freq_dev, 1.0,
                             members, mask)
            ch_m = read(state.channel, 0, members, mask)
            e = round_energy(a.astype(jnp.float32), true_freq, ch_m, ke,
                             members=members) * mask_f
        return losses, e

    # ------------------------------------------------------------------ #
    # control plane: per-cluster controller features, computable in-jit
    # ------------------------------------------------------------------ #
    def _ctl_features(self, state: FleetState, c):
        """The f32 scalars a frequency controller scores from, as pure jnp
        over the padded membership row of cluster ``c``.

        Both execution paths consume this one function — the event loop
        through the jitted ``self._features_fn`` (4 scalars pulled per
        round), the scanned path traced straight into the round scan — so
        host and in-jit ``select`` see identical device math.
        """
        loss, mean_freq, good = self._member_feats(
            state, self.tables.member_table[c], self.tables.member_mask[c],
            _read_by_id)
        return {"cluster_loss": loss, "mean_freq": mean_freq,
                "channel_good_frac": good,
                "cluster_freq": self._cluster_freq_table(state.twins)[c]}

    @staticmethod
    def _member_feats(state: FleetState, members, mask, read):
        """A cluster's masked mean twin loss, mean calibrated frequency
        and share of members in the good channel state, its rows read by
        ``read`` (the member phase's reader)."""
        twins = state.twins
        mask_f = mask.astype(jnp.float32)
        cnt = jnp.maximum(jnp.sum(mask_f), 1.0)

        def mean(leaf, fill):
            return jnp.sum(jnp.where(mask, read(leaf, fill, members, mask),
                                     0.0)) / cnt

        loss = jnp.nan_to_num(mean(twins.loss, 0.0), nan=0.0, posinf=2.3)
        mean_freq = mean(calibrated_freq(twins), 0.0)
        good = jnp.sum(jnp.where(
            mask, (read(state.channel, 1, members, mask) == 0).astype(
                jnp.float32), 0.0)) / cnt
        return loss, mean_freq, good

    def _scan_obs(self, state: FleetState, c, feats) -> jnp.ndarray:
        """The §IV-B DQN observation, pure jnp — one layout for both the
        host path (`_obs`) and the round scan.

        Slot 2 carries the Eqn-12 deficit backlog off `FleetState.queue`,
        matching the env the agent trained on (`envs._obs`; it used to hold
        the unbounded energy tally, far outside the training range).  Known
        deployment deviations from the env layout remain: the one-hot
        encodes round%10 rather than the last action, and the spent/budget
        fraction (slot 4) is not observable fleet-side — tau stands in.
        """
        tau = self.task.hidden_mean(
            jax.tree.map(lambda l: l[c], state.cluster_params),
            self.tables.x[:256])
        return ctl_policy.deploy_obs(
            feats["cluster_loss"], state.queue,
            state.round.astype(jnp.float32) / 100.0, tau,
            state.round % 10, jax.nn.one_hot(state.channel, 3).mean(0),
            feats["mean_freq"])

    # ------------------------------------------------------------------ #
    # host side: controller context
    # ------------------------------------------------------------------ #
    def _obs(self, c: int) -> jnp.ndarray:
        """DQN observation for host-side `select`: the same `_scan_obs`
        function the scanned path traces, as one jitted call."""
        return self._obs_fn(self.state, self.tables, jnp.int32(c))

    def _ctx(self, c: int) -> ControllerCtx:
        f = jax.device_get(self._features_fn(self.state, self.tables,
                                             jnp.int32(c)))
        return ControllerCtx(
            round=self._rounds, cluster=c, obs=lambda: self._obs(c),
            cluster_loss=float(f["cluster_loss"]),
            cluster_freq=float(f["cluster_freq"]),
            mean_freq=float(f["mean_freq"]),
            channel_good_frac=float(f["channel_good_frac"]),
            energy_used=self._energy_used)

    def _null_ctx(self, c: int) -> ControllerCtx:
        """Sync-free ctx for ``needs_ctx=False`` controllers; obs stays
        lazily available should a controller reach for it anyway."""
        return ControllerCtx(
            round=self._rounds, cluster=c, obs=lambda: self._obs(c),
            cluster_loss=0.0, cluster_freq=1.0, mean_freq=1.0,
            channel_good_frac=1.0, energy_used=0.0)

    # ------------------------------------------------------------------ #
    # scan-over-rounds: K rounds + in-jit controller in one lax.scan
    # ------------------------------------------------------------------ #
    def _scan_features(self, state: FleetState, c, extra, needs_obs):
        """`scan_step`'s features: `_ctl_features`, and `_scan_obs` when
        the policy reads the observation."""
        del extra
        feats = self._ctl_features(state, c)
        return feats, (self._scan_obs(state, c, feats) if needs_obs
                       else None)

    def _scan_round(self, state: FleetState, c, a_raw, extra):
        """`scan_step`'s round: `_fleet_round` over cluster ``c``'s padded
        membership row."""
        state, m = self._fleet_round(state, c, a_raw,
                                     self.tables.member_table[c],
                                     self.tables.member_mask[c])
        return state, extra, m

    def _build_scan_fn(self, K: int, pol: ctl_policy.ScanPolicy):
        def run_k(state, times, ctl, energy, tables):
            eng = self._reading(tables)
            return jax.lax.scan(
                lambda carry, _: scan_step(carry, eng._scan_features,
                                           eng._scan_round, pol.step,
                                           pol.needs_obs),
                (state, times, ctl, energy), None, length=K)

        jit_kw = dict(donate_argnums=(0,))
        if self.placement.is_sharded:
            # carry: FleetState by leaf group, the per-cluster event-time
            # vector with the cluster stack, policy carry + energy tally
            # replicated; the K stacked metrics replicate (synced once);
            # the fleet tables replicate
            repl = self.placement.replicated()
            carry_sh = (self.placement.state_shardings(self.state),
                        self.placement.sharding(self.placement.cluster_axis),
                        self.placement.tree_replicated(pol.state), repl)
            ys_sh = {k: repl for k in ("t", "cluster", "a", "dur",
                                       "consumed", "loss")}
            jit_kw.update(
                in_shardings=carry_sh + (
                    self.placement.tree_replicated(self.tables),),
                out_shardings=(carry_sh, ys_sh))
        return jax.jit(run_k, **jit_kw)

    def run_scanned(self, K: int, *, eval_final: bool = True) -> FLTrace:
        """Run exactly K asynchronous cluster rounds as one `lax.scan`.

        The whole control loop — cluster scheduling, the controller's
        `select` (via its `scan_policy()`), the fused round, the Eqn-12
        queue advance — compiles into a single device program; stacked
        per-round metrics cross the host boundary **once**, after round K.
        Per-round records carry the round's mean training loss (no
        intermediate global models exist on the host to evaluate);
        ``eval_final`` appends one evaluation record for the final model.

        Requires a mask-aware aggregator (the padded fixed-shape round) and
        a controller exposing ``scan_policy()``; use the event-heap `run`
        for exact-shape robust rules, ``sim_seconds`` cutoffs, or per-round
        evaluation.

        Consecutive calls *continue*: the per-cluster event-time vector
        carries across calls (as the round counter and energy tally always
        did), so ``run_scanned(K)`` twice produces the exact trace
        ``run_scanned(2K)`` would — the segment invariant `repro.serve`
        checkpoints and resumes on.
        """
        if not self._padded:
            raise ValueError(
                f"aggregator {type(self.aggregator).__name__} has "
                "supports_mask=False (exact-shape compiles); run_scanned "
                "needs the padded fused round — use run() instead")
        scan_policy = getattr(self.controller, "scan_policy", None)
        if scan_policy is None:
            raise ValueError(
                f"controller {type(self.controller).__name__} has no "
                "scan_policy(); use the event-heap run() instead")
        K = int(K)
        with self._obs_span("prepare"):
            pol = scan_policy()
            args = (self.state, self._scan_times, pol.state,
                    self._scan_energy_start(), self.tables)
            fn = self._scan_cache.get(K)
        if fn is None:
            fn = self._compile_scan(K, pol, args, "run_scanned")
        (state, times, _, energy_end), ys = self._dispatch_scan(fn, args, K)
        self.state = state
        self._scan_times = times        # schedule carries to the next call
        return self._emit_scanned_trace(ys, K, eval_final, energy_end)

    # ------------------------------------------------------------------ #
    # scanned-trace emission + the deferred host sync behind it
    # ------------------------------------------------------------------ #
    def _scan_energy_start(self) -> jnp.ndarray:
        """The f32 energy tally a scan segment starts from.  While segments
        are pending, the device-side carry continues (one f32 stream, no
        host round-trip); a flushed engine re-seeds from the exact f64
        tally so a fresh scan matches the event loop bit for bit."""
        return self._energy_dev if self._pending else jnp.float32(
            self._energy_used)

    def _flush_pending(self) -> None:
        """Fold deferred per-segment consumed stacks into the host f64
        tally — the same sequential additions the per-scan sync performs,
        just batched across segments."""
        if not self._pending:
            return
        pend, self._pending = self._pending, []
        for chunk in jax.device_get(pend):
            for ci in np.asarray(chunk, np.float32):
                self._energy_used += float(ci)

    def _emit_scanned_trace(self, ys, K: int, eval_final: bool,
                            energy_end) -> FLTrace:
        """Turn a scan segment's stacked device metrics into a trace.

        Fast path: with no trace sink attached, retention off, and no
        final evaluation (the `repro.serve` segment loop between
        checkpoints), nothing here is host-visible — the segment's
        consumed stack is queued instead of synced and the f32 energy
        carry stays device-side, so back-to-back segments run without a
        per-segment `device_get`.  Anything host-facing flushes first.
        """
        base = self._rounds
        self._rounds += K
        sync_queue = getattr(self.controller, "sync_queue", None)
        if (self.trace_sink is None and not self.trace_retain
                and not eval_final):
            self._pending.append(ys["consumed"])
            self._energy_dev = energy_end
            if sync_queue is not None:
                sync_queue(self.state.queue)
            if self.obs is not None:
                # deferred path: keep the round counter honest, but do
                # not force the per-segment sync the path exists to avoid
                self.obs.m_rounds.inc(K)
            return self._new_trace()

        with self._obs_span("flush"):
            self._flush_pending()
        with self._obs_span("host_sync", rounds=K):
            ys = jax.device_get(ys)         # the one end-of-run sync
        with self._obs_span("records"):
            # rebuild the float64 tally by the same sequential additions
            # the event loop performs (bitwise-identical cumulative
            # energies)
            cum = []
            for ci in np.asarray(ys["consumed"], np.float32):
                self._energy_used += float(ci)
                cum.append(self._energy_used)
            if sync_queue is not None:      # host controller adopts the
                sync_queue(self.state.queue)  # device-resident backlog
            if self.obs is not None:
                self.obs.on_segment(ys, K, engine=self)

            trace = self._new_trace()
            for i in range(K):
                trace.append(RoundRecord(
                    t=float(ys["t"][i]), round=base + i + 1,
                    cluster=int(ys["cluster"][i]), a=int(ys["a"][i]),
                    loss=float(ys["loss"][i]), acc=None, energy=cum[i],
                    agg_count=base + i + 1))
        if eval_final:
            with self._obs_span("eval"):
                ev = self.task.evaluate(self.state.global_params,
                                        self.data)
            if self.obs is not None:
                self.obs.on_eval(ev["loss"], ev.get("acc"))
            trace.append(RoundRecord(
                t=float(ys["t"][-1]) + float(ys["dur"][-1]),
                round=self._rounds, cluster=int(ys["cluster"][-1]),
                a=int(ys["a"][-1]), loss=ev["loss"], acc=ev.get("acc"),
                energy=self._energy_used, agg_count=self._rounds))
        return trace

    # ------------------------------------------------------------------ #
    def run(self, eval_every: float = 1.0,
            max_rounds: Optional[int] = None) -> FLTrace:
        if self.spec.execution == "scanned":
            K = max_rounds if max_rounds is not None else self.spec.rounds
            return self.run_scanned(K)
        spec = self.spec
        self._flush_pending()   # the event loop tallies energy per round
        trace = self._new_trace()
        events = [(0.0, c) for c in range(spec.clustering.n_clusters)]
        heapq.heapify(events)
        t = 0.0
        next_eval = 0.0
        done = 0
        while events and t < spec.sim_seconds:
            if max_rounds is not None and done >= max_rounds:
                break
            t, c = heapq.heappop(events)
            if t >= spec.sim_seconds:
                break
            ctx = self._ctx(c) if self._needs_ctx else self._null_ctx(c)
            a_raw = int(self.controller.select(ctx))
            self.state, metrics = self._round_fn(
                self.state, self.tables, c, a_raw, self._members[c],
                self._masks[c])
            self._rounds += 1
            done += 1
            m = jax.device_get(metrics)
            self._energy_used += float(m["consumed"])
            self.controller.observe(None, float(m["consumed"]),
                                    float(m["loss"]))
            if self.obs is not None:
                self.obs.on_round(
                    cluster=c, a=int(m["a"]), dur=float(m["dur"]),
                    consumed=float(m["consumed"]), loss=float(m["loss"]),
                    engine=self)
            heapq.heappush(events, (t + float(m["dur"]), c))
            if t >= next_eval:
                with self._obs_span("eval"):
                    ev = self.task.evaluate(self.state.global_params,
                                            self.data)
                if self.obs is not None:
                    self.obs.on_eval(ev["loss"], ev.get("acc"))
                trace.append(RoundRecord(
                    t=t, round=self._rounds, cluster=c, a=int(m["a"]),
                    loss=ev["loss"], acc=ev.get("acc"),
                    energy=self._energy_used,
                    agg_count=self._rounds))
                next_eval = t + eval_every
        return trace

    # legacy attribute views (shims, examples, tests) ------------------- #
    @property
    def rep(self):
        return self.state.rep

    @property
    def twins(self):
        return self.state.twins

    @property
    def channel(self):
        return self.state.channel

    @property
    def global_params(self):
        return self.state.global_params

    @property
    def cluster_params(self):
        return [jax.tree.map(lambda l, i=i: l[i], self.state.cluster_params)
                for i in range(self.spec.clustering.n_clusters)]

    @property
    def energy_used(self) -> float:
        self._flush_pending()
        return self._energy_used

    @property
    def agg_count(self) -> int:
        return self._rounds

    @property
    def round(self) -> int:
        return self._rounds


class DatacenterEngine:
    """Sharded fl_step (mode A/B) under the unified spec + trace schema.

    A smoke-scale driver of the datacenter path: the controller picks a_i
    per round exactly as at device scale (one pseudo-cluster ctx), trust
    reputations feed Eqn 6 inside the jit-ed step, staleness is zero
    (synchronous pods) unless the spec says otherwise.
    """

    @classmethod
    def from_spec(cls, spec: FederationSpec, *, controller, aggregator=None,
                  task, data=None, parts=None,
                  fused: Optional[bool] = None) -> "DatacenterEngine":
        # Eqn-6 trust weighting lives inside the jit-ed fl_step, and the
        # task adapter generates its own token batches: the aggregator
        # instance and the device-scale data/fused overrides are unused
        del aggregator, data, parts, fused
        return cls(spec, controller=controller, task=task)

    def __init__(self, spec: FederationSpec, *, controller, task):
        from repro.core import fl_step
        from repro.optim import adam
        self.spec = spec
        self.controller = controller
        self.task = task
        self.n_clusters = spec.clustering.n_clusters
        self.clients = max(1, spec.fleet.n_devices // self.n_clusters)
        self.opt = adam(task.lr)
        init = fl_step.build_init_fn(
            task.cfg, self.opt, mode=task.mode,
            n_clusters=self.n_clusters, clients_per_cluster=self.clients)
        self.key = jax.random.PRNGKey(spec.seed)
        self.state = init(self.key)
        self.rep = jnp.ones((self.n_clusters, self.clients))
        self._steps = {}
        self._fl = fl_step

    def _step(self, a: int):
        if a not in self._steps:
            self._steps[a] = jax.jit(self._fl.build_train_step(
                self.task.cfg, self.opt, mode=self.task.mode, local_steps=a))
        return self._steps[a]

    def run(self, eval_every: float = 1.0,
            max_rounds: Optional[int] = None) -> FLTrace:
        del eval_every                      # every round is recorded
        from repro.core.envs import OBS_DIM
        spec = self.spec
        trace = FLTrace()
        loss = float("nan")
        rounds = spec.rounds if max_rounds is None else min(spec.rounds,
                                                            max_rounds)
        for i in range(rounds):
            self.key, kb = jax.random.split(self.key)
            obs_feats = jnp.asarray([0.0 if np.isnan(loss) else loss,
                                     i / max(spec.rounds, 1), 0.0])
            ctx = ControllerCtx(
                round=i, cluster=0,
                obs=lambda f=obs_feats: jnp.pad(f, (0, OBS_DIM - 3)),
                cluster_loss=0.0 if np.isnan(loss) else loss,
                cluster_freq=1.0, mean_freq=1.0, channel_good_frac=1.0,
                energy_used=0.0)
            a = max(1, min(self.controller.select(ctx),
                           self.controller.n_actions))
            batch = self.task.make_batch(kb, self.n_clusters, self.clients)
            stale = jnp.zeros((self.n_clusters,))
            self.state, metrics = self._step(a)(
                self.state, batch, self.rep, stale)
            loss = float(jnp.mean(metrics["loss"]))
            # no energy model at datacenter scale: report zero consumption
            # (a raw step count would corrupt a Lyapunov queue's units)
            self.controller.observe(ctx, 0.0, loss)
            trace.append(RoundRecord(
                t=float(i), round=i + 1, cluster=-1, a=a, loss=loss,
                acc=None, energy=0.0, agg_count=i + 1))
        return trace

    def run_scanned(self, K: int, *, eval_final: bool = True) -> FLTrace:
        raise ValueError(
            "the datacenter engine has no scanned lowering (its round loop "
            "is already a fixed-shape jit step per round); use run()")


def default_device_data(spec: FederationSpec):
    """Synthetic non-IID federated data from the task params (the
    device-scale default when `from_spec` gets no data/parts override).

    Deterministic in ``spec.seed`` — a fresh process rebuilding an engine
    from the same spec regenerates identical data and shards, which is what
    lets `repro.serve` checkpoint only the `FleetState` and not the
    dataset.  Dispatches on the task kind: classification tasks draw the
    MNIST-shaped prototype mixture; the reconstruction task draws IoT
    telemetry and partitions it by device type (each client sees mostly one
    equipment family — non-IID in the covariates rather than the labels).
    """
    from repro.data import (dirichlet_partition, make_classification,
                            make_iot_telemetry)
    p = spec.task.params
    key = jax.random.PRNGKey(spec.seed)
    if spec.task.kind == "autoencoder-anomaly":
        data = make_iot_telemetry(
            key, n=p.get("n_samples", 2048), dim=p.get("dim", 32),
            n_types=p.get("n_types", 8), latent=p.get("latent", 4),
            anomaly_frac=p.get("anomaly_frac", 0.05),
            noise=p.get("noise", 0.05))
        parts = dirichlet_partition(key, data.device_type,
                                    spec.fleet.n_devices,
                                    alpha=p.get("dirichlet_alpha", 0.5),
                                    n_classes=p.get("n_types", 8))
        return data, parts
    data = make_classification(key, n=p.get("n_samples", 4096),
                               dim=p.get("dim", 784))
    parts = dirichlet_partition(key, data.y, spec.fleet.n_devices,
                                alpha=p.get("dirichlet_alpha", 0.5))
    return data, parts


class DeviceScaleGspmdEngine(DeviceScaleEngine):
    """The jit-sharded GSPMD path, pinned: ``scale='device-gspmd'`` runs
    `DeviceScaleEngine` itself even where a 1-D mesh would resolve to the
    cluster-major shard_map engine.  (Equivalent per-spec escape hatch:
    ``ShardingSpec.impl='gspmd'``.)"""


# `scale` resolves through the same registry mechanism as every other
# component; a new execution scale is a registration, not a facade edit
register_engine(DEVICE_SCALE)(DeviceScaleEngine)
register_engine(GSPMD_DEVICE_SCALE)(DeviceScaleGspmdEngine)
register_engine(DATACENTER_SCALE)(DatacenterEngine)
