"""Cluster-major fleets: the explicit `jax.shard_map` execution engine.

`ClusterMajorEngine` re-indexes the fleet **cluster-major** at build time:
a static device permutation lays every cluster's members (plus padding
slots) out as one contiguous, shard-aligned range of every fleet-axis
`FleetState` leaf.  Slot ``c*S + j`` holds device ``member_table[c, j]``
(ascending original ids; the sentinel ``n`` marks padding), so the
membership gathers that force GSPMD to all-gather across shards under
k-means assignments become plain `dynamic_slice`s at ``c*S`` — shard-local
by construction.

The round is then an explicit `shard_map` over one mesh axis instead of a
jit the SPMD partitioner carves up:

  * replicated pre-work — RNG splits, the Alg.-2 tolerance bound — runs on
    every shard from replicated scalars (bit-identical math, no traffic);
  * the owning shard runs the *parent's* member phase (its helpers: batch
    gather, local SGD, Eqns 4-5 trust, Eqn-6 aggregation, energy) under a
    `lax.cond`, with a member reader that takes its slot block by
    `dynamic_slice`; non-owners skip;
  * exactly **two** collectives cross shards per round: one `psum` of a
    packed scalar/metrics vector (consumed energy, round loss, the drop
    flag, the straggle factor, the Eqn-19 normalizer, the per-cluster
    frequency table, channel one-hot counts) and one `psum` of the
    Eqn-19 staleness-weighted partial sums of the cluster-parameter stack.
    The HLO test pins this: zero ``all-gather``s, at most two
    ``all-reduce``s in the compiled round.

A stable inverse permutation (``slot_of_orig``) keeps the public surface
in original device ids: `resumable_state` / `restore_resumable` speak the
unsharded checkpoint layout (checkpoints are interchangeable across
engines), the legacy ``rep``/``twins``/``channel`` views un-permute, and
fault/malicious tables are gathered by original id inside the round so
`FaultSpec` subsets mean the same devices on every engine.

Arbitrary ``(n_devices, n_clusters)`` run on any 1-D mesh: the cluster
axis pads to ``ceil(C/G)*G`` with masked sentinel clusters (event time
+inf, Eqn-19 weight 0) and the fleet axis pads to ``C_pad * S`` sentinel
slots; the padding applied is logged at build.

Exactness contract (asserted by tests/test_cluster_engine.py): on a
1-shard mesh the trace is **bit-identical** to the unsharded engine for
all three controllers on both execution paths (with the jnp aggregation
path, ``use_kernel=False``).  Across G>1 shards, scheduling, actions,
counters, energies and the frequency table stay exact (single-contributor
psums add zeros; integer counts are exact); only the Eqn-19 sums
reassociate, so losses match to rtol ~1e-5 where matmuls run in f32.  On a
TPU at the default matmul precision (bf16 passes for f32 operands) local
training amplifies those ulps: over the 40 rounds of the paper's
16-device MLP scenario on four v5e chips, losses differed by up to 1.7e-4
relative while every exact column still matched (``chip_smoke.py --chips
4`` checks both precisions).

Two deliberate replications keep the collective count at two: the Markov
channel draws the full-fleet categorical on every shard (the transition
matrix is state-independent — identical rows — so all shards compute the
*parent's* original-order draw and gather their slots; builds reject
custom matrices that break this), and the controller features/psum ride
the same owner-gated pattern with one extra psum on the *event* path only
(the scanned path fuses it into the round's program).
"""
from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.control import policy as ctl_policy
from repro.control import queue as ctl_queue
from repro.core.twin import MEMBER_FILLS, TwinState, calibrate, calibrated_freq

from .engine import DeviceScaleEngine, FleetState, scan_step
from .placement import shard_map_placement
from .spec import ShardingSpec

log = logging.getLogger("repro.cluster")

_STALE_BASE = jnp.e / 2         # Eqn-19 decay base (trust.staleness_weights)
_EPS = 1e-8                     # its normalizer epsilon
# FleetState fields sharded over the mesh axis; the rest replicate
_SHARDED_FIELDS = ("twins", "rep", "channel", "cluster_params", "cluster_ts")


class ClusterMajorEngine(DeviceScaleEngine):
    """`DeviceScaleEngine` on a cluster-major layout + explicit shard_map.

    Selected by ``ShardingSpec.impl='shard_map'`` (the default for 1-D
    meshes) through ``DeviceScaleEngine.from_spec``; the jit-sharded GSPMD
    path stays registry-selectable as ``impl='gspmd'`` / the
    ``'device-gspmd'`` scale.
    """

    def __init__(self, spec, data, parts, *, controller, aggregator, task,
                 fused=None, assign=None):
        if fused is False:
            raise ValueError(
                "the cluster-major shard_map engine is fused-only "
                "(fused=False runs the eager reference round); use "
                "impl='gspmd' or an unsharded spec for the reference path")
        if not bool(getattr(aggregator, "supports_mask", False)):
            raise ValueError(
                f"aggregator {type(aggregator).__name__} has "
                "supports_mask=False (exact-shape compiles); the "
                "cluster-major engine runs the padded fixed-shape round "
                "only — pick a mask-aware rule or impl='gspmd'")
        # build the exact unsharded engine first (same RNG stream, same
        # k-means/membership/malicious tables), then permute + commit
        base = dataclasses.replace(spec, sharding=ShardingSpec())
        super().__init__(base, data, parts, controller=controller,
                         aggregator=aggregator, task=task, fused=True,
                         assign=assign)
        self.spec = spec
        n = spec.fleet.n_devices
        C = spec.clustering.n_clusters
        spec.sharding.validate(n, C)
        self.placement = shard_map_placement(spec.sharding)
        self._ax = spec.sharding.resolved_axes()[0]
        G = int(spec.sharding.mesh[0])
        S = int(self.tables.member_table.shape[1])
        C_pad = -(-C // G) * G          # auto-pad: masked sentinel clusters
        n_pad = C_pad * S               # ... and sentinel device slots
        self._n, self._C, self._S, self._G = n, C, S, G
        self._C_pad, self._C_loc, self._n_pad = C_pad, C_pad // G, n_pad

        # the identical-rows channel trick (module docstring) needs a
        # state-independent transition matrix
        trans = np.asarray(self._trans)
        if not (trans == trans[0]).all():
            raise ValueError(
                "cluster-major engine: the channel transition matrix must "
                "be state-independent (identical rows) so every shard can "
                "reproduce the original-order channel draw; got distinct "
                "rows — use impl='gspmd'")

        # slot -> original device id (sentinel n at padding) and its
        # stable inverse; member_table rows are ascending original ids
        oos = np.full((n_pad,), n, np.int32)
        oos[:C * S] = np.asarray(self.tables.member_table).reshape(-1)
        real = oos < n
        soo = np.zeros((n,), np.int32)
        soo[oos[real]] = np.nonzero(real)[0].astype(np.int32)
        self._oos = jnp.asarray(oos)
        self._slot_of_orig = jnp.asarray(soo)
        if C_pad != C or n_pad != n:
            log.info(
                "cluster-major padding: %d clusters -> %d and %d devices "
                "-> %d slots (mesh %s, %d member slots per cluster); "
                "sentinel clusters carry event time +inf and Eqn-19 "
                "weight 0, sentinel device slots are masked everywhere",
                C, C_pad, n, n_pad, tuple(spec.sharding.mesh), S)

        # permute the freshly built state cluster-major and commit it, the
        # per-shard static tables and the replicated fleet tables to the
        # mesh; every compiled entry point takes `_statics` as arguments
        self.state = self._shard_cm(self._permute_state(self.state))
        dev = NamedSharding(self.placement.mesh, P(self._ax))
        repl = NamedSharding(self.placement.mesh, P())
        self.tables = jax.tree.map(lambda v: self._commit(v, repl),
                                   self.tables)
        self._statics = tuple(self._commit(v, dev) for v in (
            self._oos,
            self.tables.misbehaving.at[self._oos].get(mode="fill",
                                                      fill_value=0.0),
            jnp.asarray(real),                   # slot validity (n_pad,)
            jnp.asarray(np.arange(C_pad) < C),   # cluster validity (C_pad,)
        )) + (self.tables,)
        self._scan_times = jnp.concatenate([
            jnp.zeros((C,), jnp.float32),
            jnp.full((C_pad - C,), jnp.inf, jnp.float32)])

        # Eqn-19 flatten spec: the psum'd global average travels as one
        # packed vector and unflattens to the global_params pytree
        gleaves, self._gp_def = jax.tree_util.tree_flatten(
            self.state.global_params)
        self._gp_shapes = [l.shape for l in gleaves]
        self._gp_sizes = [int(np.prod(l.shape)) if l.shape else 1
                          for l in gleaves]
        self._gp_dtypes = [l.dtype for l in gleaves]

        # swap the execution paths in for the parent's jits
        self._event_fn = None
        self._round_fn = self._cm_event_round
        self._scan_cache = {}
        # (the parent's ``tables`` argument is `self.tables`, which rides
        # in `_statics`)
        self._feo_fn = self._build_feats_fn()
        self._features_fn = lambda state, tables, c: self._feo_fn(
            state, self._ftbl, self._ch3, c, *self._statics)[0]
        self._obs_fn = lambda state, tables, c: self._feo_fn(
            state, self._ftbl, self._ch3, c, *self._statics)[1]
        self._aux_fn = self._build_aux_fn()
        # carried replicated per-round aggregates: the (C_pad,) straggler
        # frequency table and the fleet channel one-hot fractions, each
        # recomputed inside the round so the next round (and the host
        # controller ctx) reads them without touching sharded leaves
        self._ftbl, self._ch3 = self._aux_fn(self.state, *self._statics)

    # ------------------------------------------------------------------ #
    # layout plumbing
    # ------------------------------------------------------------------ #
    def _cm_pspecs(self):
        """Full-structure FleetState PartitionSpec tree (no prefix trees)."""
        dev, rep = P(self._ax), P()
        return FleetState(**{
            f: jax.tree.map(
                lambda _, s=(dev if f in _SHARDED_FIELDS else rep): s,
                getattr(self.state, f))
            for f in FleetState._fields})

    @staticmethod
    def _commit(x, sh):
        """Commit one leaf to a NamedSharding; multi-process safe.

        Under `jax.distributed` the mesh spans processes, where
        `jax.device_put` refuses non-addressable shardings — every
        process holds the identical host value (same seeds, same
        program), so assembling the global array from per-process local
        shards is exact.  Typed PRNG keys detour through key_data (the
        callback path wants a plain dtype)."""
        if sh.is_fully_addressable:
            return jax.device_put(x, sh)
        if jnp.issubdtype(jnp.asarray(x).dtype, jax.dtypes.prng_key):
            data = ClusterMajorEngine._commit(jax.random.key_data(x), sh)
            return jax.random.wrap_key_data(data)
        arr = np.asarray(jax.device_get(x))
        return jax.make_array_from_callback(arr.shape, sh,
                                            lambda idx: arr[idx])

    def _shard_cm(self, state):
        mesh = self.placement.mesh
        dev = NamedSharding(mesh, P(self._ax))
        rep = NamedSharding(mesh, P())
        sh = FleetState(**{
            f: jax.tree.map(
                lambda _, s=(dev if f in _SHARDED_FIELDS else rep): s,
                getattr(state, f))
            for f in FleetState._fields})
        return jax.tree.map(self._commit, state, sh)

    def _permute_state(self, fleet: FleetState) -> FleetState:
        """Original-order (n, C) state -> cluster-major (n_pad, C_pad)."""
        oos = self._oos

        def perm(x, fill):
            return jnp.asarray(x).at[oos].get(mode="fill", fill_value=fill)

        tw = TwinState(*[perm(x, fill)
                         for x, fill in zip(fleet.twins, MEMBER_FILLS)])
        padc = self._C_pad - self._C

        def pad_c(l):
            l = jnp.asarray(l)
            if not padc:
                return l
            return jnp.concatenate(
                [l, jnp.zeros((padc,) + l.shape[1:], l.dtype)], axis=0)

        return FleetState(
            twins=tw, rep=perm(fleet.rep, 1.0),
            channel=perm(jnp.asarray(fleet.channel, jnp.int32), 0),
            cluster_params=jax.tree.map(pad_c, fleet.cluster_params),
            global_params=fleet.global_params,
            cluster_ts=pad_c(jnp.asarray(fleet.cluster_ts, jnp.float32)),
            queue=fleet.queue, round=fleet.round, key=fleet.key)

    # ------------------------------------------------------------------ #
    # shard-local building blocks
    # ------------------------------------------------------------------ #
    def _local_freq_table(self, twins, mskslot_l):
        """This shard's (C_loc,) straggler frequency table — bit-equal per
        row to the parent's `_cluster_freq_table` (min is order-free)."""
        f = calibrated_freq(twins).reshape(self._C_loc, self._S)
        m = mskslot_l.reshape(self._C_loc, self._S)
        fmin = jnp.min(jnp.where(m, f, jnp.inf), axis=1)
        return jnp.where(m.any(axis=1), fmin, 1.0)

    def _row_scatter(self, full, vals, maskd, lo, mine):
        """Masked (S,)-row scatter at slot ``lo``, applied only on the
        owning shard — the slot-space twin of ``.at[members].set(mode=
        'drop')``."""
        old = jax.lax.dynamic_slice(full, (lo,), (self._S,))
        new = jnp.where(maskd, vals.astype(full.dtype), old)
        upd = jax.lax.dynamic_update_slice(full, new, (lo,))
        return jnp.where(mine, upd, full)

    # ------------------------------------------------------------------ #
    # the per-shard round (traced under shard_map)
    # ------------------------------------------------------------------ #
    def _slot_reader(self, lo):
        """The member phase's ``read`` on this layout: the cluster's slot
        block at ``lo``, masked to the fills a gather by original id gives
        (not what a padding slot carries, e.g. its alpha tally, which
        drifts +1 a round)."""
        def read(leaf, fill, members, mask):
            del members
            sl = jax.lax.dynamic_slice(leaf, (lo,), (self._S,))
            return jnp.where(mask, sl, fill)
        return read

    def _cm_round_local(self, state, ftbl, ch3, c, a_raw,
                        oos_l, misb_l, mskslot_l, validc_l, tables):
        """One cluster round, shard-local: the parent `_fleet_round` split
        into replicated pre-work, an owner-gated member phase, and two
        psums.  Returns (state', ftbl', ch3', metrics)."""
        del ch3                         # consumed by the caller's next obs
        spec = self.spec
        fm = self.faults
        S, C_loc = self._S, self._C_loc
        ax = self._ax
        g = jax.lax.axis_index(ax)
        cl = jnp.clip(c - g * C_loc, 0, C_loc - 1)   # local cluster row
        lo = cl * S                                   # local slot offset
        mine = (c >= g * C_loc) & (c < (g + 1) * C_loc)
        eng = self._reading(tables)

        # --- replicated pre-work: exact parent RNG stream + Alg.-2 bound
        # (the max runs over the *real* clusters: sentinel rows hold 1.0)
        keys = eng._round_keys(state.key)
        a = eng._bounded_action(a_raw, ftbl[:self._C], c, state.round)

        # --- owner phase: the parent's member round.  The full-fleet
        # `FleetTables` (member/partition/data/attackers) ride in
        # replicated, so gathers by *original* id are identical to the
        # parent's; only sharded FleetState leaves read through the slot
        # reader.
        def owner(_):
            read = self._slot_reader(lo)
            members, mask, mask_f, cnt, empty = eng._member_mask(
                keys.flt, tables.member_table[c], tables.member_mask[c])
            new, batch, cur, _, rep_m, w = eng._member_update(
                state, cl, members, mask, a, keys, read, None)
            # Eqn 6 as the spec asks: the masked Pallas kernel when
            # use_kernel is on, the jnp rule otherwise
            agg = eng._cluster_aggregate(new, w, mask, cur, keys.dp, mask_f,
                                         cnt)
            losses, e = eng._member_cost(state, new, batch, a, members, mask,
                                         mask_f, keys.energy, read)
            # the straggle *factor* (straggle() multiplies its dur arg, so
            # dur=1 extracts it); applied post-psum as dur * factor — the
            # exact product the parent computes
            stretch = (fm.straggle(keys.flt, jnp.float32(1.0), mask,
                                   members)
                       if fm.may_straggle else jnp.float32(1.0))
            empty = (empty.astype(jnp.float32) if fm.may_drop
                     else jnp.float32(0.0))
            return {"agg": agg, "losses": losses, "e": e, "rep_m": rep_m,
                    "maskd": mask_f, "consumed": jnp.sum(e),
                    "loss": jnp.sum(losses * mask_f) / cnt,
                    "empty": empty, "stretch": stretch}

        def skip(_):
            zS = jnp.zeros((S,), jnp.float32)
            z = jnp.float32(0.0)
            return {"agg": jax.tree.map(jnp.zeros_like, state.global_params),
                    "losses": zS, "e": zS, "rep_m": zS, "maskd": zS,
                    "consumed": z, "loss": z, "empty": z,
                    "stretch": jnp.float32(0.0)}

        out = jax.lax.cond(mine, owner, skip, None)
        maskd = out["maskd"] > 0.5      # post-drop member validity

        # --- all-shard state updates (slot space)
        rep_new = self._row_scatter(state.rep, out["rep_m"], maskd, lo, mine)
        loss_new = self._row_scatter(state.twins.loss, out["losses"],
                                     maskd, lo, mine)
        e_row = self._row_scatter(jnp.zeros_like(state.twins.energy),
                                  out["e"], maskd, lo, mine)
        tw = state.twins._replace(
            loss=loss_new, energy=state.twins.energy + e_row,
            alpha=state.twins.alpha + (1.0 - misb_l),
            beta=state.twins.beta + misb_l)
        if spec.fleet.calibrate_dt:
            tw = calibrate(tw)

        # identical-rows channel: every shard reproduces the parent's
        # original-order full-fleet draw, then gathers its own slots
        new_ch = jax.random.categorical(
            keys.channel,
            jnp.broadcast_to(jnp.log(self._trans[0] + 1e-12), (self._n, 3)),
            axis=-1)
        channel_l = new_ch.at[oos_l].get(mode="fill", fill_value=0)

        rnd = state.round + 1
        rnd_f = rnd.astype(jnp.float32)

        def set_row(L, v):
            upd = jax.lax.dynamic_update_slice(
                L, v.astype(L.dtype)[None], (cl,) + (0,) * (L.ndim - 1))
            return jnp.where(mine, upd, L)

        cp1 = jax.tree.map(set_row, state.cluster_params, out["agg"])
        ts_new = jnp.where(
            mine, jax.lax.dynamic_update_slice(state.cluster_ts,
                                               rnd_f[None], (cl,)),
            state.cluster_ts)

        # --- psum #1: packed scalars + the recomputed frequency table
        # (disjoint per-shard blocks; exact) + channel one-hot counts
        # (integer-valued; exact)
        ftbl_loc = self._local_freq_table(tw, mskslot_l)
        mskslot_f = mskslot_l.astype(jnp.float32)
        w_un = _STALE_BASE ** (-(rnd_f - ts_new)) * validc_l.astype(
            jnp.float32)
        vec = jnp.concatenate([
            jnp.stack([out["consumed"], out["loss"], out["empty"],
                       out["stretch"], jnp.sum(w_un)]),
            jax.lax.dynamic_update_slice(
                jnp.zeros((self._C_pad,), jnp.float32), ftbl_loc,
                (g * C_loc,)),
            jnp.sum(jax.nn.one_hot(channel_l, 3) * mskslot_f[:, None],
                    axis=0),
        ])
        vec = jax.lax.psum(vec, ax)
        consumed = vec[0]
        loss_m = vec[1]
        empty_ps = vec[2]
        stretch_ps = vec[3]
        den = vec[4]
        ftbl_new = vec[5:5 + self._C_pad]
        ch3_new = vec[5 + self._C_pad:] / self._n

        # --- psum #2: Eqn-19 staleness-weighted global average over the
        # (sharded) cluster stack, as one packed partial-sum vector
        w_norm = w_un / (den + _EPS)
        parts = [
            jnp.sum(l * w_norm.reshape((-1,) + (1,) * (l.ndim - 1)).astype(
                l.dtype), axis=0).reshape(-1)
            for l in jax.tree_util.tree_leaves(cp1)]
        gvec = jax.lax.psum(jnp.concatenate(parts), ax)
        offs = np.cumsum([0] + self._gp_sizes)
        gleaves = [gvec[offs[i]:offs[i + 1]].reshape(
            self._gp_shapes[i]).astype(self._gp_dtypes[i])
            for i in range(len(self._gp_sizes))]
        gparams = jax.tree_util.tree_unflatten(self._gp_def, gleaves)
        cp2 = jax.tree.map(set_row, cp1, gparams)

        if fm.may_drop:
            # fully-dropped cluster: graceful skip, exactly as the parent
            empty_b = empty_ps > 0.5
            revert = lambda old, newv: jax.tree.map(
                lambda o, v: jnp.where(empty_b, o, v), old, newv)
            consumed = jnp.where(empty_b, 0.0, consumed)
            tw = revert(state.twins, tw)
            rep_new = revert(state.rep, rep_new)
            cp2 = revert(state.cluster_params, cp2)
            gparams = revert(state.global_params, gparams)
            ts_new = revert(state.cluster_ts, ts_new)
            ftbl_new = jnp.where(empty_b, ftbl, ftbl_new)

        queue = ctl_queue.advance(state.queue, consumed,
                                  self._queue_per_slot)
        dur = a.astype(jnp.float32) / jnp.maximum(ftbl_new[c], 1e-6)
        if fm.may_straggle:
            dur = dur * stretch_ps

        new_state = FleetState(
            twins=tw, rep=rep_new, channel=channel_l, cluster_params=cp2,
            global_params=gparams, cluster_ts=ts_new, queue=queue,
            round=rnd, key=keys.key)
        metrics = {"a": a, "dur": dur, "consumed": consumed,
                   "loss": loss_m}
        return new_state, ftbl_new, ch3_new, metrics

    # ------------------------------------------------------------------ #
    # controller features / observation, shard-local
    # ------------------------------------------------------------------ #
    def _cm_feats_local(self, state, ftbl, ch3, c, mskslot_l, needs_obs,
                        tables):
        """Parent `_ctl_features` + `_scan_obs` over the owner's slot
        block; one (4,) psum replicates the scalars (+zeros: exact).  The
        observation is None unless ``needs_obs``."""
        S, C_loc = self._S, self._C_loc
        g = jax.lax.axis_index(self._ax)
        cl = jnp.clip(c - g * C_loc, 0, C_loc - 1)
        lo = cl * S
        mine = (c >= g * C_loc) & (c < (g + 1) * C_loc)

        def owner(_):
            mask = jax.lax.dynamic_slice(mskslot_l, (lo,), (S,))
            loss, mean_freq, good = self._member_feats(
                state, None, mask, self._slot_reader(lo))
            if needs_obs:
                row = jax.tree.map(
                    lambda l: jax.lax.dynamic_index_in_dim(
                        l, cl, 0, keepdims=False), state.cluster_params)
                tau = self.task.hidden_mean(row, tables.x[:256])
            else:
                tau = jnp.float32(0.0)
            return jnp.stack([loss, mean_freq, good, tau])

        vec = jax.lax.psum(
            jax.lax.cond(mine, owner,
                         lambda _: jnp.zeros((4,), jnp.float32), None),
            self._ax)
        feats = {"cluster_loss": vec[0], "mean_freq": vec[1],
                 "channel_good_frac": vec[2], "cluster_freq": ftbl[c]}
        obs48 = (ctl_policy.deploy_obs(
            vec[0], state.queue, state.round.astype(jnp.float32) / 100.0,
            vec[3], state.round % 10, ch3, vec[1]) if needs_obs else None)
        return feats, obs48

    # ------------------------------------------------------------------ #
    # compiled entry points
    # ------------------------------------------------------------------ #
    def _build_event_fn(self):
        pspecs = self._cm_pspecs()
        dev = P(self._ax)
        m_specs = {"a": P(), "dur": P(), "consumed": P(), "loss": P()}
        sm = jax.shard_map(
            self._cm_round_local, mesh=self.placement.mesh,
            in_specs=(pspecs, P(), P(), P(), P(), dev, dev, dev, dev, P()),
            out_specs=(pspecs, P(), P(), m_specs),
            check_vma=False)
        return jax.jit(sm)

    def _cm_event_round(self, state, tables, c, a_raw, members=None,
                        mask=None):
        """Event-path round: `_round_fn`-compatible host wrapper (the
        members/mask args of the parent's signature are unused — the
        layout *is* the membership — and ``tables``, `self.tables`, rides
        in `_statics`)."""
        del tables, members, mask
        if self._event_fn is None:
            self._event_fn = self._build_event_fn()
        state, self._ftbl, self._ch3, m = self._event_fn(
            state, self._ftbl, self._ch3, jnp.int32(c),
            jnp.asarray(a_raw, jnp.int32), *self._statics)
        return state, m

    def _build_feats_fn(self):
        pspecs = self._cm_pspecs()
        dev = P(self._ax)

        def fn(state, ftbl, ch3, c, oos_l, misb_l, mskslot_l, validc_l,
               tables):
            del oos_l, misb_l, validc_l
            return self._cm_feats_local(state, ftbl, ch3, c, mskslot_l,
                                        True, tables)

        f_specs = {"cluster_loss": P(), "mean_freq": P(),
                   "channel_good_frac": P(), "cluster_freq": P()}
        sm = jax.shard_map(
            fn, mesh=self.placement.mesh,
            in_specs=(pspecs, P(), P(), P(), dev, dev, dev, dev, P()),
            out_specs=(f_specs, P()), check_vma=False)
        return jax.jit(sm)

    def _build_aux_fn(self):
        """(ftbl, ch3) from a freshly committed state — used at build and
        after `restore_resumable` (both are round-start equivalents)."""
        pspecs = self._cm_pspecs()
        dev = P(self._ax)
        C_pad, C_loc, n = self._C_pad, self._C_loc, self._n
        ax = self._ax

        def aux(state, oos_l, misb_l, mskslot_l, validc_l, tables):
            del oos_l, misb_l, validc_l, tables
            g = jax.lax.axis_index(ax)
            f_loc = self._local_freq_table(state.twins, mskslot_l)
            msk_f = mskslot_l.astype(jnp.float32)
            vec = jnp.concatenate([
                jax.lax.dynamic_update_slice(
                    jnp.zeros((C_pad,), jnp.float32), f_loc, (g * C_loc,)),
                jnp.sum(jax.nn.one_hot(state.channel, 3) * msk_f[:, None],
                        axis=0)])
            vec = jax.lax.psum(vec, ax)
            return vec[:C_pad], vec[C_pad:] / n

        sm = jax.shard_map(aux, mesh=self.placement.mesh,
                       in_specs=(pspecs, dev, dev, dev, dev, P()),
                       out_specs=(P(), P()), check_vma=False)
        return jax.jit(sm)

    # ------------------------------------------------------------------ #
    # scanned execution: the whole K-round scan inside ONE shard_map
    # ------------------------------------------------------------------ #
    def _build_scan_fn(self, K: int, pol: ctl_policy.ScanPolicy):
        pspecs = self._cm_pspecs()
        dev = P(self._ax)
        ctl_spec = jax.tree.map(lambda _: P(), pol.state)

        def local(state, times, ctl, energy, ftbl, ch3,
                  oos_l, misb_l, mskslot_l, validc_l, tables):
            def features(state, c, extra, needs_obs):
                ftbl, ch3 = extra
                return self._cm_feats_local(state, ftbl, ch3, c, mskslot_l,
                                            needs_obs, tables)

            def round_fn(state, c, a_raw, extra):
                ftbl, ch3 = extra
                state, ftbl, ch3, m = self._cm_round_local(
                    state, ftbl, ch3, c, a_raw,
                    oos_l, misb_l, mskslot_l, validc_l, tables)
                return state, (ftbl, ch3), m

            return jax.lax.scan(
                lambda carry, _: scan_step(carry, features, round_fn,
                                           pol.step, pol.needs_obs),
                (state, times, ctl, energy, ftbl, ch3), None, length=K)

        ys_specs = {k: P() for k in ("t", "cluster", "a", "dur",
                                     "consumed", "loss")}
        sm = jax.shard_map(
            local, mesh=self.placement.mesh,
            in_specs=(pspecs, P(), ctl_spec, P(), P(), P(),
                      dev, dev, dev, dev, P()),
            out_specs=((pspecs, P(), ctl_spec, P(), P(), P()), ys_specs),
            check_vma=False)
        return jax.jit(sm)

    def run_scanned(self, K: int, *, eval_final: bool = True):
        scan_policy = getattr(self.controller, "scan_policy", None)
        if scan_policy is None:
            raise ValueError(
                f"controller {type(self.controller).__name__} has no "
                "scan_policy(); use the event-heap run() instead")
        K = int(K)
        with self._obs_span("prepare"):
            pol = scan_policy()
            args = (self.state, self._scan_times, pol.state,
                    self._scan_energy_start(), self._ftbl, self._ch3,
                    *self._statics)
            fn = self._scan_cache.get(K)
        if fn is None:
            fn = self._compile_scan(K, pol, args, "cm_run_scanned")
        (state, times, _, energy_end, ftbl, ch3), ys = self._dispatch_scan(
            fn, args, K)
        self.state = state
        self._scan_times = times
        self._ftbl, self._ch3 = ftbl, ch3
        return self._emit_scanned_trace(ys, K, eval_final, energy_end)

    # ------------------------------------------------------------------ #
    # checkpoints + legacy views: original device order at the boundary
    # ------------------------------------------------------------------ #
    def resumable_state(self) -> dict:
        """Unsharded layout (original device order, real clusters only) —
        interchangeable with `DeviceScaleEngine` checkpoints in both
        directions."""
        self._flush_pending()
        soo = self._slot_of_orig
        st = self.state
        fleet = FleetState(
            twins=jax.tree.map(lambda l: l[soo], st.twins),
            rep=st.rep[soo], channel=st.channel[soo],
            cluster_params=jax.tree.map(lambda l: l[:self._C],
                                        st.cluster_params),
            global_params=st.global_params,
            cluster_ts=st.cluster_ts[:self._C],
            queue=st.queue, round=st.round, key=st.key)
        return {"fleet": fleet, "times": self._scan_times[:self._C]}

    def restore_resumable(self, tree: dict, *, rounds: int,
                          energy: float) -> None:
        fleet = tree["fleet"]
        if not isinstance(fleet, FleetState):
            fleet = FleetState(*fleet) if isinstance(fleet, (tuple, list)) \
                else FleetState(**fleet)
        self.state = self._shard_cm(self._permute_state(fleet))
        self._scan_times = jnp.concatenate([
            jnp.asarray(tree["times"], jnp.float32),
            jnp.full((self._C_pad - self._C,), jnp.inf, jnp.float32)])
        self._rounds = int(rounds)
        self._energy_used = float(energy)
        self._pending = []
        self._energy_dev = jnp.float32(energy)
        self._ftbl, self._ch3 = self._aux_fn(self.state, *self._statics)
        sync_queue = getattr(self.controller, "sync_queue", None)
        if sync_queue is not None:
            sync_queue(self.state.queue)

    def obs_state_summary(self) -> dict:
        """Telemetry state summary, masked to real device slots: sentinel
        slots (cluster-major padding) carry the `MEMBER_FILLS` values and
        would skew the reputation stats if reduced over naively."""
        if self._obs_summary_fn is None:
            def summarize(state, valid):
                rep = state.rep
                v = valid.astype(jnp.float32)
                nv = jnp.sum(v)
                return {
                    "queue_deficit": state.queue,
                    "reputation_min": jnp.min(
                        jnp.where(valid, rep, jnp.inf)),
                    "reputation_mean": jnp.sum(rep * v) / nv,
                    "reputation_max": jnp.max(
                        jnp.where(valid, rep, -jnp.inf)),
                    "twin_beta_sum": jnp.sum(state.twins.beta * v)}
            self._obs_summary_fn = jax.jit(summarize)
        out = jax.device_get(self._obs_summary_fn(
            self.state, self._statics[2]))
        return {k: float(v) for k, v in out.items()}

    @property
    def scan_times(self):
        return self._scan_times[:self._C]

    @property
    def rep(self):
        return self.state.rep[self._slot_of_orig]

    @property
    def twins(self):
        return jax.tree.map(lambda l: l[self._slot_of_orig],
                            self.state.twins)

    @property
    def channel(self):
        return self.state.channel[self._slot_of_orig]
