"""The serving ``Policy`` protocol and its four implementations.

A policy is everything an :class:`~repro.serve.session.OnlineBandit`
session needs to turn a request batch into choices and fold feedback
back — four hooks over a policy-specific state pytree:

  init(shards=1)                  -> state        (global shapes, laid
                                     out for ``shards`` row shards)
  gather_score(state, idx)        -> (w, minv_eff, occ) rows for the
                                     fused choose, gathered per request
  apply_pass(state, idx, x, r, live, be)
                                  -> state        one masked feedback
                                     pass; ``live`` rows have DISTINCT
                                     user ids (the session's duplicate
                                     decomposition guarantees it), so a
                                     single fused rank-1 sweep is exact
  refresh(col, state, key)        -> state        the periodic stage

Policies are hashable NamedTuples of Python scalars (like the backend
engines), so the session can close jit-compiled transactions over them.
None of the scoring / update / refresh math lives here: the clustered
policies call the stage bodies (``runtime.stages.beta_gate`` /
``mix_scores`` / ``stage2_refresh``), linucb is ``linucb.user_vector`` +
the fused engine, and dccb reuses ``core.dccb.lagged_score`` /
``buffered_push`` / ``gossip_round``.

| policy     | scores with                      | refresh                    |
|------------|----------------------------------|----------------------------|
| `distclub` | beta gate: own vs cluster stats  | stage-2 (prune+CC+reduce)  |
| `club`     | cluster stats always             | stage-2 (prune+CC+reduce)  |
| `linucb`   | own stats always                 | none                       |
| `dccb`     | lagged buffered stats            | one gossip round           |

``gather_score`` doubles as the CATALOG-RETRIEVAL statistics hook: the
``(w, minv_eff, occ)`` rows it returns are exactly what the streaming
top-K engine scores the item catalog with (``serve.step_catalog``), so
every policy serves two-stage against a ``core.catalog.Catalog`` with no
policy-specific retrieval code — the shortlist is ranked by the same
mixed statistics the fused choose would score a caller-supplied slate
with.

The clustered policies adopt the engine's FROZEN-snapshot semantics: the
per-user cluster statistics (``uMcinv``/``ubc``/``umean_occ``) are taken
at refresh time and held constant until the next refresh — exactly what
stages 3/4 of the offline drivers read.  (The pre-redesign serving layer
instead advanced ``clusters.seen`` live between refreshes; see the README
migration notes.)
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..core import dccb, distclub, linucb
from ..core.backend import BackendConfig, InteractBackend
from ..core.types import BanditHyper, ClusterStats, DistCLUBState, GraphState
from ..kernels.graph import ops as graph_ops
from ..kernels.spdinv.ops import spd_inverse
from ..runtime import stages

from jax.sharding import PartitionSpec as P

POLICIES = ("distclub", "dccb", "club", "linucb")


class ServeCfg(NamedTuple):
    """Static facts of one serving session (hashable -> jit-static).

    ``engine`` is the run-level `InteractBackend` — the dispatch decision
    (kind, interpret, padding policy) resolved ONCE at session creation
    and the single source of those facts (the graph engine for refresh
    follows ``engine.kind``/``engine.interpret``); the session derives
    the request-batch-width engine from it per traced batch shape via
    ``engine.with_users``."""

    n_users: int
    d: int
    n_candidates: int
    hyper: BanditHyper
    refresh_every: int      # interactions between refreshes; <= 0 = never
    engine: "InteractBackend"


def _scatter_rows(array, tgt, rows):
    """Masked row scatter: ``tgt`` >= n_local rows are dropped."""
    return array.at[tgt].set(rows, mode="drop")


def _row_major(array):
    """``array`` laid out row-major, users on the major dimension.  A TPU
    stores a narrow ``[n, d, d]`` table with its users on the lanes, where
    a row gather works but a row scatter does not: XLA would copy the
    whole table into row-major order for every pass's scatter."""
    return with_layout_constraint(
        array, Layout(major_to_minor=tuple(range(array.ndim))))


def _rank1_pass(Minv, b, occ, idx, x, r, live, be):
    """One fused masked Sherman-Morrison pass over gathered rows,
    scattered back for the live (distinct-user) rows only — the shared
    feedback body of every LinUCB-statistics policy.  The tables stay
    row-major across passes (``_row_major``)."""
    Minv, b = _row_major(Minv), _row_major(b)
    Minv2, b2 = be.update_inv(Minv[idx], b[idx], x, r, live)
    tgt = jnp.where(live, idx, occ.shape[0])
    return (_row_major(_scatter_rows(Minv, tgt, Minv2)),
            _row_major(_scatter_rows(b, tgt, b2)),
            occ.at[tgt].add(1, mode="drop"))


# ---------------------------------------------------------------------------
# distclub / club — the clustered policies (stage-engine refresh)
# ---------------------------------------------------------------------------


class ClusteredState(NamedTuple):
    """DistCLUB/CLUB serving state: LinUCB rows + packed graph + the
    frozen per-user stage-2 snapshots.  ``[n_local, ...]`` arrays are the
    sharded ones; ``labels`` and the scalars are replicated."""

    Minv: jnp.ndarray         # [n_local, d, d]
    b: jnp.ndarray            # [n_local, d]
    occ: jnp.ndarray          # [n_local] i32
    adj: jnp.ndarray          # [n_local, ceil(n/32)] uint32 packed rows,
    #                           at graph_ops.stored_shape(n_local, n)
    labels: jnp.ndarray       # [n] i32 replicated
    uMcinv: jnp.ndarray       # [n_local, d, d]  frozen cluster snapshot
    ubc: jnp.ndarray          # [n_local, d]
    umean_occ: jnp.ndarray    # [n_local] f32
    since_refresh: jnp.ndarray  # [] i32
    comm_bytes: jnp.ndarray     # [] f32 modeled stage-2 traffic


class ClusteredPolicy(NamedTuple):
    cfg: ServeCfg
    use_beta: bool            # True = distclub (beta gate), False = club
    # NamedTuples compare as plain tuples, so policies of different
    # classes over the same cfg would otherwise collide in the session's
    # compiled-transaction cache — the kind tag keeps them distinct.
    kind: str = "clustered"

    @property
    def name(self) -> str:
        return "distclub" if self.use_beta else "club"

    @property
    def has_refresh(self) -> bool:
        return True

    def init(self, shards: int = 1) -> ClusteredState:
        """The fresh state; ``shards`` row shards lay the graph out a
        block per shard (``graph_ops.init_stored_adj``)."""
        n, d = self.cfg.n_users, self.cfg.d
        # HBM-dominant [n, d, d] state lives in the session's Precision
        # state dtype (f32 default -> these astype calls are no-ops)
        sdt = self.cfg.engine.precision.jnp_state
        eye = jnp.broadcast_to(jnp.eye(d, dtype=jnp.float32),
                               (n, d, d)).astype(sdt)
        return ClusteredState(
            Minv=eye,
            b=jnp.zeros((n, d), jnp.float32),
            occ=jnp.zeros((n,), jnp.int32),
            adj=graph_ops.init_stored_adj(n, shards),
            labels=jnp.zeros((n,), jnp.int32),   # one big cluster initially
            uMcinv=eye,
            ubc=jnp.zeros((n, d), jnp.float32),
            umean_occ=jnp.zeros((n,), jnp.float32),
            since_refresh=jnp.zeros((), jnp.int32),
            comm_bytes=jnp.zeros((), jnp.float32),
        )

    def occ_of(self, state: ClusteredState):
        return state.occ

    def gather_score(self, state: ClusteredState, idx):
        # gather reduced-precision rows, then upcast once for the f32
        # user-vector solve and the fused choose (no-op under f32)
        Minv = state.Minv[idx].astype(jnp.float32)
        b, occ = state.b[idx], state.occ[idx]
        uMcinv = state.uMcinv[idx].astype(jnp.float32)
        ubc = state.ubc[idx]
        v_own = linucb.user_vector(Minv, b)
        v_clu = linucb.user_vector(uMcinv, ubc)
        if self.use_beta:
            use_own = stages.beta_gate(self.cfg.hyper, occ,
                                       state.umean_occ[idx])
        else:
            use_own = jnp.zeros(occ.shape, bool)     # CLUB: cluster always
        w, minv_eff = stages.mix_scores(use_own, v_own, v_clu, Minv, uMcinv)
        return w, minv_eff, occ

    def apply_pass(self, state: ClusteredState, idx, x, r, live, be):
        Minv, b, occ = _rank1_pass(state.Minv, state.b, state.occ,
                                   idx, x, r, live, be)
        return state._replace(Minv=Minv, b=b, occ=occ)

    def refresh(self, col, state: ClusteredState, key) -> ClusteredState:
        del key                                       # deterministic stage
        cfg = self.cfg
        n_local = state.occ.shape[0]
        gb = BackendConfig(kind=cfg.engine.kind,
                           precision=cfg.engine.precision
                           ).graph(n_local, cfg.n_users,
                                   interpret=cfg.engine.interpret)
        res = stages.stage2_refresh(col, gb, cfg.hyper, cfg.d,
                                    state.Minv, state.b, state.occ,
                                    state.adj)
        return state._replace(
            adj=res.adj, labels=res.labels,
            uMcinv=res.uMcinv.astype(state.uMcinv.dtype), ubc=res.ubc,
            umean_occ=res.umean_occ,
            comm_bytes=state.comm_bytes + res.comm_bytes,
        )

    def state_specs(self, axes) -> ClusteredState:
        s, r = P(axes), P()
        return ClusteredState(Minv=s, b=s, occ=s, adj=s, labels=r,
                              uMcinv=s, ubc=s, umean_occ=s,
                              since_refresh=r, comm_bytes=r)


# ---------------------------------------------------------------------------
# linucb — the per-user baseline (Li et al.; no clustering, no refresh)
# ---------------------------------------------------------------------------


class LinUCBServeState(NamedTuple):
    Minv: jnp.ndarray           # [n_local, d, d]
    b: jnp.ndarray              # [n_local, d]
    occ: jnp.ndarray            # [n_local] i32
    since_refresh: jnp.ndarray  # [] i32 (counted for parity; never fires)


class LinUCBPolicy(NamedTuple):
    cfg: ServeCfg
    kind: str = "linucb"      # cache-key discriminator (see ClusteredPolicy)

    @property
    def name(self) -> str:
        return "linucb"

    @property
    def has_refresh(self) -> bool:
        return False

    def init(self, shards: int = 1) -> LinUCBServeState:
        del shards                    # no graph: rows shard as they are
        n, d = self.cfg.n_users, self.cfg.d
        sdt = self.cfg.engine.precision.jnp_state
        eye = jnp.broadcast_to(jnp.eye(d, dtype=jnp.float32),
                               (n, d, d)).astype(sdt)
        return LinUCBServeState(
            Minv=eye,
            b=jnp.zeros((n, d), jnp.float32),
            occ=jnp.zeros((n,), jnp.int32),
            since_refresh=jnp.zeros((), jnp.int32),
        )

    def occ_of(self, state: LinUCBServeState):
        return state.occ

    def gather_score(self, state: LinUCBServeState, idx):
        Minv = state.Minv[idx].astype(jnp.float32)
        b, occ = state.b[idx], state.occ[idx]
        return linucb.user_vector(Minv, b), Minv, occ

    def apply_pass(self, state: LinUCBServeState, idx, x, r, live, be):
        Minv, b, occ = _rank1_pass(state.Minv, state.b, state.occ,
                                   idx, x, r, live, be)
        return state._replace(Minv=Minv, b=b, occ=occ)

    def refresh(self, col, state, key):
        del col, key
        return state

    def state_specs(self, axes) -> LinUCBServeState:
        s, r = P(axes), P()
        return LinUCBServeState(Minv=s, b=s, occ=s, since_refresh=r)


# ---------------------------------------------------------------------------
# dccb — the buffered-gossip baseline (Korda et al.)
# ---------------------------------------------------------------------------


class DCCBServeState(NamedTuple):
    core: dccb.DCCBState        # full DCCB record (dense adj, buffers)
    since_refresh: jnp.ndarray  # [] i32


class DCCBPolicy(NamedTuple):
    """DCCB as a serving policy: lagged buffered scoring, refresh = one
    gossip round.  Request-driven adaptation of the lockstep driver: the
    ring-buffer cursor advances once per feedback pass, and inactive
    users keep their pending slot entries buffered until their next
    active pass pops them (strictly longer lag, never lost updates).
    Single-host only — gossip does per-edge scatter updates on the dense
    graph, which is deliberately not sharded (see ``core.dccb``)."""

    cfg: ServeCfg
    kind: str = "dccb"        # cache-key discriminator (see ClusteredPolicy)

    @property
    def name(self) -> str:
        return "dccb"

    @property
    def has_refresh(self) -> bool:
        return True

    @property
    def L(self) -> int:
        return self.cfg.hyper.buffer_size

    def init(self, shards: int = 1) -> DCCBServeState:
        del shards                    # single-host only
        return DCCBServeState(
            core=dccb.init_state(self.cfg.n_users, self.cfg.d, self.L),
            since_refresh=jnp.zeros((), jnp.int32),
        )

    def occ_of(self, state: DCCBServeState):
        return state.core.occ

    def gather_score(self, state: DCCBServeState, idx):
        w, Minv = dccb.lagged_score(state.core.Mw[idx], state.core.bw[idx])
        return w, Minv, state.core.occ[idx]

    def apply_pass(self, state: DCCBServeState, idx, x, r, live, be):
        del be                       # buffer pushes are plain adds, not S-M
        n_local = state.core.occ.shape[0]
        d = x.shape[1]
        tgt = jnp.where(live, idx, n_local)
        x_full = jnp.zeros((n_local, d), x.dtype).at[tgt].set(x, mode="drop")
        r_full = jnp.zeros((n_local,), x.dtype).at[tgt].set(r, mode="drop")
        m_full = jnp.zeros((n_local,), bool).at[tgt].set(live, mode="drop")
        core = dccb.buffered_push(state.core, x_full, r_full, m_full, self.L)
        return state._replace(core=core)

    def refresh(self, col, state: DCCBServeState, key) -> DCCBServeState:
        del col                                       # single-host only
        core = dccb.gossip_round(state.core, key, self.cfg.hyper, self.L,
                                 self.cfg.d)
        return state._replace(core=core)

    def state_specs(self, axes):
        raise NotImplementedError(
            "dccb serving is single-host only (dense gossip graph)")


# ---------------------------------------------------------------------------
# construction + offline interop
# ---------------------------------------------------------------------------


def make_cfg(n_users: int, d: int, hyper: BanditHyper, *,
             refresh_every: int = 0, backend: str | None = None,
             interpret: bool | None = None, block_users: int = 256,
             precision=None) -> ServeCfg:
    """Resolve the engine dispatch once per session: ``backend`` via
    ``REPRO_BACKEND`` / TPU-auto and ``precision`` (a ``Precision``, a
    preset name, or None) via ``REPRO_PRECISION`` — both through
    ``core.backend.BackendConfig.create``.  The resolved precision rides
    in ``cfg.engine.precision`` and is the single source for the state
    dtype, catalog kernels and checkpoint tagging."""
    engine = BackendConfig.create(backend, precision).interact(
        n_users, d, hyper.n_candidates, block_users=block_users,
        interpret=interpret)
    return ServeCfg(n_users=n_users, d=d, n_candidates=hyper.n_candidates,
                    hyper=hyper, refresh_every=refresh_every, engine=engine)


def get_policy(name: str, cfg: ServeCfg):
    if name == "distclub":
        return ClusteredPolicy(cfg, use_beta=True)
    if name == "club":
        return ClusteredPolicy(cfg, use_beta=False)
    if name == "linucb":
        return LinUCBPolicy(cfg)
    if name == "dccb":
        return DCCBPolicy(cfg)
    raise ValueError(f"unknown policy {name!r}; want one of {POLICIES}")


def from_distclub_state(state: DistCLUBState) -> ClusteredState:
    """Warm-start a serving session from an offline ``distclub.run``
    state: per-user snapshots are gathered exactly as stage 3 would."""
    uMcinv, ubc, umean_occ = distclub.serving_snapshot(state)
    return ClusteredState(
        Minv=state.lin.Minv, b=state.lin.b, occ=state.lin.occ,
        adj=state.graph.adj, labels=state.graph.labels,
        uMcinv=uMcinv, ubc=ubc, umean_occ=umean_occ,
        since_refresh=jnp.zeros((), jnp.int32),
        comm_bytes=state.comm_bytes,
    )


def to_distclub_state(state: ClusteredState, hyper: BanditHyper,
                      d: int) -> DistCLUBState:
    """The public offline record from a serving state (label tables are
    rebuilt from the per-user rows; M recovered from Minv)."""
    n = state.occ.shape[0]
    Minv = state.Minv.astype(jnp.float32)     # offline record is f32
    M = spd_inverse(Minv)
    lin = linucb.LinUCBState(M=M, Minv=Minv, b=state.b, occ=state.occ)
    eye = jnp.eye(d, dtype=jnp.float32)
    labels = state.labels
    Mc = jax.ops.segment_sum(M - eye, labels, num_segments=n) + eye
    bc = jax.ops.segment_sum(state.b, labels, num_segments=n)
    size = jax.ops.segment_sum(jnp.ones_like(labels), labels, num_segments=n)
    seen = jax.ops.segment_sum(state.occ, labels, num_segments=n)
    stats = ClusterStats(Mc=Mc, Mcinv=spd_inverse(Mc), bc=bc,
                         size=size, seen=seen)
    rounds = jnp.full((n,), hyper.sigma, jnp.int32)
    return DistCLUBState(
        lin=lin, graph=GraphState(adj=state.adj, labels=labels),
        clusters=stats, u_rounds=rounds, c_rounds=rounds,
        comm_bytes=state.comm_bytes,
    )
