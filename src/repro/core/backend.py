"""Backend dispatch for the interaction, graph and retrieval engines.

The bandit hot loop is two operations per round — *choose* (UCB scores →
argmax → gather the chosen context) and *update* (rank-1 Sherman-Morrison
on the per-user statistics); stage 2 is two graph sweeps — *prune* (CLUB
edge deletion) and *CC hops* (min-label propagation); catalog serving adds
*shortlist* (streaming UCB top-K over the item catalog).  This module
selects between:

  ``reference``  the pure-jnp math in ``repro.core.linucb`` /
                 ``repro.kernels.graph.ref`` (CPU/GPU, and the numerical
                 oracle everywhere), and
  ``pallas``     the fused TPU kernels in ``repro.kernels.interact`` /
                 ``repro.kernels.rank1`` / ``repro.kernels.graph``
                 (``interpret=True`` off-TPU, so tier-1 still exercises
                 the kernel path).

Selection: explicit ``kind=`` argument > ``REPRO_BACKEND`` env var
("reference" | "pallas" | "auto") > "auto" (pallas iff running on TPU).

Precision: the engines additionally carry a :class:`Precision` policy —
which dtype the HBM-traffic-dominant state is STORED in (per-user ``Minv``
d^2 blocks, catalog embedding tiles), independent of the f32 the MXU/VPU
compute in.  Kernels upcast inside VMEM (``x.astype(f32)`` on a loaded
block; int8 catalog tiles additionally multiply a per-slot scale), so the
HBM stream shrinks 2x (bf16) / ~4x (int8) while every contraction still
accumulates in f32.  ``Precision.f32`` — the default — stores everything
in f32; every ``astype(float32)`` on an f32 array is a trace-time no-op,
so the f32 program is BIT-IDENTICAL to the pre-precision code.  Selection
mirrors the kind flag: explicit ``precision=`` argument > the
``REPRO_PRECISION`` env var ("f32" | "bf16" | "int8") > f32, resolved in
exactly one place (:func:`resolve_precision`).

Construction: one unified surface — ``BackendConfig(kind, precision)``
(build via :meth:`BackendConfig.create`, which resolves both flags) with
``.interact`` / ``.graph`` / ``.retrieval`` methods replacing the three
historical factories.  ``get_backend`` / ``get_graph_backend`` /
``get_retrieval_backend`` remain as thin deprecated wrappers for one PR.

Padding happens once per run, not once per call: the backend precomputes
the padded dims (users to the block multiple, d/K to sublane/lane
multiples) at construction, the drivers pad the scan-carried state a single
time per stage via ``pad_gram``/``pad_vec``/..., and every kernel entry
point short-circuits when handed pre-aligned arrays.  Only the per-step
context tensor (fresh every round) is padded inside the loop.  All padding
is exact: zero feature columns contribute nothing to scores or updates,
padded candidates are masked to -inf inside the choose kernel, and padded
users carry a zero budget so their mask is always off.

The backend is a NamedTuple of Python scalars — hashable, so drivers can
thread it through ``jax.jit`` as a static argument.
"""
from __future__ import annotations

import os
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import pad
from ..kernels.graph import ops as graph_ops
from ..kernels.interact import ops as interact_ops
from ..kernels.rank1 import ops as rank1_ops
from ..kernels.rank1.ref import rank1_update_inv_ref
from ..kernels.topk import ops as topk_ops
from ..kernels.topk.ref import tile_bounds, topk_ref, topk_ref_pruned
from . import clustering, linucb

_ENV_FLAG = "REPRO_BACKEND"
_PRECISION_ENV_FLAG = "REPRO_PRECISION"

_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}
_STATE_DTYPES = ("f32", "bf16")             # Minv blocks (SPD: never int8)
_CATALOG_DTYPES = ("f32", "bf16", "int8")   # embedding tiles


class Precision(NamedTuple):
    """Storage-precision policy for the HBM-dominant state.

    A NamedTuple of Python scalars — hashable, so it rides inside the
    engine NamedTuples through ``jax.jit`` static arguments and the
    serving layer's lru-cached transactions compile once per policy.

    ``state_dtype``    per-user ``Minv`` d^2 blocks ("f32" | "bf16");
                       ``b``/``occ`` stay f32/i32 — they are O(d) per
                       user and exactness there keeps occ-style metrics
                       exact.
    ``catalog_dtype``  catalog embedding banks ("f32" | "bf16" | "int8";
                       int8 adds a per-slot f32 scale — see
                       ``core.catalog``).
    ``accum_dtype``    in-VMEM accumulation for the MXU contractions;
                       always "f32" today (kept explicit so the policy
                       records the numeric contract, not just storage).
    ``scale_block``    int8 scale granularity at initial quantization:
                       slots are grouped in blocks of this size sharing
                       one scale (churn-added rows get row-granular
                       scales; the stored array is per-slot either way).
    """

    state_dtype: str = "f32"
    catalog_dtype: str = "f32"
    accum_dtype: str = "f32"
    scale_block: int = 512

    @property
    def jnp_state(self):
        return _DTYPES[self.state_dtype]

    @property
    def jnp_catalog(self):
        return _DTYPES[self.catalog_dtype]

    @property
    def jnp_accum(self):
        return _DTYPES[self.accum_dtype]


# presets — the names the REPRO_PRECISION env flag accepts
Precision.f32 = Precision()
Precision.bf16 = Precision(state_dtype="bf16", catalog_dtype="bf16")
Precision.int8 = Precision(state_dtype="bf16", catalog_dtype="int8")
_PRECISION_PRESETS = {"f32": Precision.f32, "bf16": Precision.bf16,
                      "int8": Precision.int8}


def resolve_precision(precision=None) -> Precision:
    """THE one resolution point for the precision policy: explicit
    argument (a :class:`Precision` or a preset name) > ``REPRO_PRECISION``
    env var > f32.  Mirrors :func:`resolve_kind`."""
    if precision is None:
        precision = os.environ.get(_PRECISION_ENV_FLAG) or "f32"
    if isinstance(precision, str):
        if precision not in _PRECISION_PRESETS:
            raise ValueError(
                f"unknown precision {precision!r}; want "
                f"{'|'.join(_PRECISION_PRESETS)} or a Precision instance"
            )
        precision = _PRECISION_PRESETS[precision]
    if not isinstance(precision, Precision):
        raise TypeError(f"precision must be a Precision or preset name, "
                        f"got {type(precision).__name__}")
    if precision.state_dtype not in _STATE_DTYPES:
        raise ValueError(f"state_dtype {precision.state_dtype!r}; "
                         f"want {'|'.join(_STATE_DTYPES)}")
    if precision.catalog_dtype not in _CATALOG_DTYPES:
        raise ValueError(f"catalog_dtype {precision.catalog_dtype!r}; "
                         f"want {'|'.join(_CATALOG_DTYPES)}")
    if precision.accum_dtype != "f32":
        raise ValueError("accum_dtype must be 'f32' (MXU contractions "
                         "accumulate in f32)")
    if precision.scale_block < 1:
        raise ValueError(f"scale_block must be >= 1, "
                         f"got {precision.scale_block}")
    return precision


class InteractBackend(NamedTuple):
    """Fused-interaction engine for fixed (n, d, K) run shapes."""

    kind: str          # "reference" | "pallas"
    n: int             # logical users
    d: int             # logical feature dim
    K: int             # logical candidates per round
    n_pad: int         # users rounded to the block multiple
    d_pad: int         # d rounded to the sublane multiple
    K_pad: int         # K rounded to the lane multiple
    block_users: int
    interpret: bool    # run Pallas in interpret mode (CPU fallback)
    precision: Precision = Precision()   # storage policy for Minv state

    # ---- pad-once helpers (all trace-time no-ops when already padded, and
    # ---- identities for the reference backend) ------------------------------

    def pad_users(self, a: jnp.ndarray, fill=0) -> jnp.ndarray:
        """Pad the leading user axis n -> n_pad with ``fill``."""
        if a.shape[0] == self.n_pad:
            return a
        pad = [(0, self.n_pad - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, pad, constant_values=fill)

    def unpad_users(self, a: jnp.ndarray) -> jnp.ndarray:
        return a if a.shape[0] == self.n else a[: self.n]

    def pad_vec(self, a: jnp.ndarray) -> jnp.ndarray:
        """[n, d] -> [n_pad, d_pad] zero-padded."""
        if a.shape == (self.n_pad, self.d_pad):
            return a
        return jnp.pad(a, ((0, self.n_pad - a.shape[0]),
                           (0, self.d_pad - a.shape[1])))

    def unpad_vec(self, a: jnp.ndarray) -> jnp.ndarray:
        if a.shape == (self.n, self.d):
            return a
        return a[: self.n, : self.d]

    def pad_gram(self, a: jnp.ndarray) -> jnp.ndarray:
        """[n, d, d] -> [n_pad, d_pad, d_pad], identity on the padded diag
        (keeps padded Gram/inverse-Gram blocks well-conditioned; the real
        d x d block never mixes with the pad because padded x columns are
        zero)."""
        if a.shape == (self.n_pad, self.d_pad, self.d_pad):
            return a
        n, d = a.shape[0], a.shape[1]
        out = jnp.pad(a, ((0, self.n_pad - n), (0, self.d_pad - d),
                          (0, self.d_pad - d)))
        i = jnp.arange(d, self.d_pad)
        out = out.at[:, i, i].set(1.0)
        if n < self.n_pad:
            j = jnp.arange(d)
            out = out.at[n:, j, j].set(1.0)
        return out

    def unpad_gram(self, a: jnp.ndarray) -> jnp.ndarray:
        if a.shape == (self.n, self.d, self.d):
            return a
        return a[: self.n, : self.d, : self.d]

    def pad_ctx(self, a: jnp.ndarray) -> jnp.ndarray:
        """[n, K, d] -> [n_pad, K_pad, d_pad] zero-padded (per step)."""
        if a.shape == (self.n_pad, self.K_pad, self.d_pad):
            return a
        return jnp.pad(a, ((0, self.n_pad - a.shape[0]),
                           (0, self.K_pad - a.shape[1]),
                           (0, self.d_pad - a.shape[2])))

    def with_users(self, n: int) -> "InteractBackend":
        """The same engine re-fit to a different leading (user/request)
        width — d, K and the dispatch decision are kept.  The serving
        layer uses this to derive a request-batch-width engine from the
        run-level one: the kind is resolved once per session, the width
        once per traced batch shape."""
        if n == self.n:
            return self
        if self.kind == "reference":
            return self._replace(n=n, n_pad=n)
        n_pad, d_pad, K_pad, bu = pad.padded_dims(n, self.d, self.K,
                                                  self.block_users)
        return self._replace(n=n, n_pad=n_pad, d_pad=d_pad, K_pad=K_pad,
                             block_users=bu)

    def with_candidates(self, K: int) -> "InteractBackend":
        """The same engine re-fit to a different slate width.  The
        catalog serving path uses this to run the final fused choose over
        a ``K_short`` shortlist with the session's run-level dispatch."""
        if K == self.K:
            return self
        if self.kind == "reference":
            return self._replace(K=K, K_pad=K)
        n_pad, d_pad, K_pad, bu = pad.padded_dims(self.n, self.d, K,
                                                  self.block_users)
        return self._replace(K=K, n_pad=n_pad, K_pad=K_pad, block_users=bu)

    # ---- the two hot-loop operations ---------------------------------------

    def choose(self, w, Minv, contexts, occ, alpha):
        """(x, choice) at the width of ``w`` (padded state in, padded out;
        logical-width inputs get logical-width outputs).

        Pallas kind: one kernel computes scores, argmax and the chosen-x
        gather in a single VMEM residency; the [n, K] score tensor never
        reaches HBM.  Reference kind: the seed linucb math.
        """
        if self.kind == "reference":
            # astype on an f32 array is a trace-time no-op — bf16 state
            # upcasts here so reference and pallas score the same f32 math
            choice = linucb.choose_batch(w, Minv.astype(jnp.float32),
                                         contexts, occ, alpha)
            x = jnp.take_along_axis(
                contexts, choice[:, None, None], axis=1
            )[:, 0]
            return x, choice
        choice, x = interact_ops.choose(
            self.pad_vec(w), self.pad_gram(Minv), self.pad_ctx(contexts),
            self.pad_users(occ), alpha,
            use_pallas=True, block_users=self.block_users,
            interpret=self.interpret, k_live=self.K,
        )
        return x[: w.shape[0], : w.shape[1]], choice[: w.shape[0]]

    def update_inv(self, Minv, b, x, r, mask):
        """M-free masked update (the sharded runtime carries no M)."""
        if self.kind == "reference":
            return rank1_update_inv_ref(Minv, b, x, r, mask)
        return rank1_ops.rank1_update_inv(
            Minv, b, x, r, mask,
            use_pallas=True, block_users=self.block_users,
            interpret=self.interpret,
        )


class GraphBackend(NamedTuple):
    """Stage-2 graph engine over the bit-packed adjacency.

    Operates on uint32 rows stored at ``graph_ops.stored_shape(n_rows,
    n_cols)``, the kernels' padded extents (layout:
    ``repro.kernels.graph.ref``).  ``n_rows == n_cols`` in the single-host
    drivers; the sharded runtime builds one backend per shard with
    ``n_rows = n_local`` and reuses the same kernels on its row shard.
    Like ``InteractBackend`` this is a NamedTuple of Python scalars, so it
    threads through ``jax.jit`` as a static argument.
    """

    kind: str          # "reference" | "pallas"
    n_rows: int        # adjacency rows held by this caller
    n_cols: int        # global user count (columns)
    row_block: int     # reference-path row blocking (lax.map tile)
    interpret: bool

    @property
    def words(self) -> int:
        """uint32 words per adjacency row."""
        return graph_ops.packed_words(self.n_cols)

    def init_adj(self, row_offset: int = 0) -> jnp.ndarray:
        """Fully-connected packed adjacency minus self edges, at its
        stored shape (``graph_ops.stored_shape``)."""
        rows, words = graph_ops.stored_shape(self.n_rows, self.n_cols)
        return graph_ops.init_packed_adj(self.n_rows, self.n_cols,
                                         n_words=words,
                                         row_offset=row_offset,
                                         rows_pad=rows)

    def pack(self, dense: jnp.ndarray) -> jnp.ndarray:
        return graph_ops.pack_bits(dense, self.words)

    def unpack(self, packed: jnp.ndarray) -> jnp.ndarray:
        return graph_ops.unpack_bits(packed, self.n_cols)

    def _opts(self):
        return dict(use_pallas=self.kind == "pallas",
                    interpret=self.interpret, row_block=self.row_block)

    def prune_rows(self, adj, v_i, occ_i, v_j, occ_j, gamma):
        """AND the CLUB keep-mask into the packed rows.  The [n, n] f32
        distance matrix stays in VMEM (pallas) / a row slab (reference)."""
        cb_i = clustering.cb_width(occ_i)
        cb_j = clustering.cb_width(occ_j)
        return graph_ops.prune_packed(adj, v_i, cb_i, v_j, cb_j, gamma,
                                      **self._opts())

    def prune(self, adj, v, occ, gamma):
        """Square single-host prune (rows == columns)."""
        return self.prune_rows(adj, v, occ, v, occ, gamma)

    def cc_hop(self, adj, labels_self, labels_j):
        """One min-label hop over the packed rows (no pointer doubling)."""
        return graph_ops.cc_hop_packed(adj, labels_self, labels_j,
                                       **self._opts())

    def cc(self, adj) -> jnp.ndarray:
        """Connected components of the square packed graph: delegates to
        the engine's CC loop (``runtime.stages.connected_components``)
        with null collectives — ONE hop-sequence definition for CLUB, the
        single-host DistCLUB driver and the sharded runtime, identical to
        the dense ``clustering.connected_components`` oracle."""
        # call-time import: runtime.stages imports repro.core modules, so
        # a module-level import here would be order-sensitive.
        from ..runtime import collectives, stages
        return stages.connected_components(
            collectives.NullCollectives(), self, adj, self.n_cols,
            row0=0, n_local=self.n_rows,
        )


class RetrievalBackend(NamedTuple):
    """Catalog-scale retrieval engine: streaming UCB top-K shortlists.

    Scores a persistent ``[N_items, d]`` catalog for a batch of users
    with the same M-free statistics the fused choose reads
    (``theta . x + alpha sqrt(x' Minv x) sqrt(log1p(occ))``) and returns
    each user's ``K_short`` best (scores + item ids) WITHOUT ever
    materializing the ``[n, N_items]`` score matrix — the Pallas kernel
    keeps the running shortlist in revisited VMEM output blocks across
    item tiles, the jnp reference streams item tiles under ``lax.map`` /
    ``lax.scan``.  Like the other engines this is a NamedTuple of Python
    scalars, hashable and jit-static.

    The item-sharded runtime builds ONE backend and calls it per shard
    with that shard's catalog slice and ``row0_items = shard * n_local``;
    selection is by (score, id) value, so per-shard shortlists merged by
    the serving layer equal the single-host shortlist exactly (see
    ``kernels/topk/ref.py``).
    """

    kind: str          # "reference" | "pallas"
    d: int             # feature dim
    K_short: int       # shortlist length per user
    block_users: int   # pallas user block
    block_items: int   # pallas item tile
    row_block: int     # reference user-row blocking (lax.map tile)
    item_block: int    # reference item tile (lax.scan step)
    interpret: bool
    precision: Precision = Precision()   # storage policy (Minv + catalog)

    def shortlist(self, w, Minv, occ, items, live, alpha, row0_items=0,
                  scales=None):
        """(scores [n, K_short], ids [n, K_short] i32 GLOBAL item ids).

        ``row0_items`` is the global id of the catalog slice's first row
        (``axis_index * n_local`` on an item-sharded mesh).  Entries that
        hold no live item (underfull catalog / all-retired tile) keep
        score -inf and id -1.  ``items`` may be stored f32/bf16/int8 —
        int8 needs the per-slot ``scales [N]`` f32 array; the kernels
        dequantize tile-by-tile inside VMEM.
        """
        if self.kind == "reference":
            s, i = topk_ref(w, Minv, occ, items, live, alpha, self.K_short,
                            row_block=self.row_block,
                            item_block=self.item_block, scales=scales)
        else:
            s, i = topk_ops.topk(w, Minv, occ, items, live, alpha,
                                 self.K_short, use_pallas=True,
                                 block_users=self.block_users,
                                 block_items=self.block_items,
                                 interpret=self.interpret, scales=scales)
        i = jnp.where(jnp.isfinite(s), i + row0_items, -1)
        return s, i

    def shortlist_pruned(self, w, Minv, occ, items_sorted, live_sorted,
                         ids_sorted, tile_mu, tile_r, tile_xn, tile_n,
                         alpha, scales_sorted=None):
        """Cluster-pruned shortlist over a SORTED catalog slice
        (``core.itemclub`` builds the layout): computes the per-(user,
        tile) UCB upper bounds and streams only the tiles that can still
        beat each user block's running shortlist floor.

        Returns ``(scores [n, K_short], ids [n, K_short] i32 GLOBAL slot
        ids, tiles_skipped [] i32, tile_visits_total [] i32)`` with the
        shortlist BIT-EQUAL to :meth:`shortlist` over the unsorted slice
        — ``ids_sorted`` carries the original slot ids (already global
        on a sharded catalog: the cluster tables are replicated and each
        shard takes its position range), so tie-breaks match exactly.
        The caller is responsible for epoch freshness: these tables
        describe the bank they were built from, and a stale table's
        bounds are wrong — ``serve`` falls back to :meth:`shortlist`
        when ``clusters.epoch != catalog.epoch``."""
        with jax.named_scope("tile_bounds"):
            tb = tile_bounds(w, Minv, occ, alpha, tile_mu, tile_r, tile_xn,
                             tile_n)
        with jax.named_scope("retrieve"):
            if self.kind == "reference":
                s, i, skipped, total = topk_ref_pruned(
                    w, Minv, occ, items_sorted, live_sorted, ids_sorted,
                    alpha, self.K_short, tb, row_block=self.row_block,
                    scales=scales_sorted)
            else:
                s, i, skipped, total = topk_ops.topk_pruned(
                    w, Minv, occ, items_sorted, live_sorted, ids_sorted,
                    alpha, self.K_short, tb, use_pallas=True,
                    block_users=self.block_users, row_block=self.row_block,
                    interpret=self.interpret, scales=scales_sorted)
            i = jnp.where(jnp.isfinite(s), i, -1)
        return s, i, skipped, total


def resolve_kind(kind: str | None = None) -> str:
    kind = kind or os.environ.get(_ENV_FLAG) or "auto"   # "" -> auto
    if kind == "auto":
        kind = "pallas" if jax.default_backend() == "tpu" else "reference"
    if kind not in ("reference", "pallas"):
        raise ValueError(
            f"unknown backend {kind!r}; want reference|pallas|auto"
        )
    return kind


class BackendConfig(NamedTuple):
    """THE backend-construction surface: one resolved (kind, precision)
    pair building every engine.  Replaces the three historical factories
    (``get_backend`` / ``get_graph_backend`` / ``get_retrieval_backend``),
    whose keyword surfaces had drifted apart; those names remain as thin
    deprecated wrappers for one PR.

        cfg = BackendConfig.create()              # env flags / auto
        be  = cfg.interact(n, d, K)
        gb  = cfg.graph(n_local, n_users)
        rb  = cfg.retrieval(d, K_short)

    Hashable (a NamedTuple of a str and a Precision), so it can ride
    through jit-static arguments like the engines themselves.
    """

    kind: str
    precision: Precision

    @classmethod
    def create(cls, kind: str | None = None,
               precision=None) -> "BackendConfig":
        """Resolve both selection flags — ``kind`` via
        :func:`resolve_kind` (``REPRO_BACKEND``), ``precision`` via
        :func:`resolve_precision` (``REPRO_PRECISION``)."""
        return cls(kind=resolve_kind(kind),
                   precision=resolve_precision(precision))

    def _interpret(self, interpret: bool | None) -> bool:
        if interpret is None:
            return jax.default_backend() != "tpu"
        return interpret

    def interact(self, n: int, d: int, K: int, *, block_users: int = 256,
                 interpret: bool | None = None) -> InteractBackend:
        """Fused-interaction engine for a run's (n, d, K); padded dims
        fixed here once."""
        if self.kind == "reference":
            n_pad, d_pad, K_pad, bu = n, d, K, block_users
        else:
            n_pad, d_pad, K_pad, bu = pad.padded_dims(n, d, K, block_users)
        return InteractBackend(
            kind=self.kind, n=n, d=d, K=K,
            n_pad=n_pad, d_pad=d_pad, K_pad=K_pad,
            block_users=bu, interpret=self._interpret(interpret),
            precision=self.precision,
        )

    def graph(self, n_rows: int, n_cols: int | None = None, *,
              row_block: int = 256,
              interpret: bool | None = None) -> GraphBackend:
        """Stage-2 graph engine for a run's row/column extents.  The
        adjacency is bit-packed — there is nothing to store in reduced
        precision, so the graph engine ignores ``precision``."""
        return GraphBackend(
            kind=self.kind, n_rows=n_rows,
            n_cols=n_rows if n_cols is None else n_cols,
            row_block=row_block,
            interpret=self._interpret(interpret),
        )

    def retrieval(self, d: int, K_short: int, *, block_users: int = 128,
                  block_items: int = 512, row_block: int = 8,
                  item_block: int = 4096,
                  interpret: bool | None = None) -> RetrievalBackend:
        """Catalog-scale retrieval engine (streaming UCB top-K)."""
        return RetrievalBackend(
            kind=self.kind, d=d, K_short=K_short,
            block_users=block_users, block_items=block_items,
            row_block=row_block, item_block=item_block,
            interpret=self._interpret(interpret),
            precision=self.precision,
        )


# ---------------------------------------------------------------------------
# deprecated factory names — thin wrappers for one PR (the bandit_service
# playbook: keep the old names importable with a pointer, remove next PR)
# ---------------------------------------------------------------------------

_warned: set[str] = set()


def _deprecated(old: str, new: str) -> None:
    if old in _warned:      # once per process — tests stay quiet
        return
    _warned.add(old)
    warnings.warn(
        f"repro.core.backend.{old} is deprecated; build engines via "
        f"BackendConfig.create(kind, precision).{new} instead",
        DeprecationWarning, stacklevel=3,
    )


def get_backend(
    n: int,
    d: int,
    K: int,
    kind: str | None = None,
    *,
    block_users: int = 256,
    interpret: bool | None = None,
    precision=None,
) -> InteractBackend:
    """Deprecated — use ``BackendConfig.create(kind, precision).interact``."""
    _deprecated("get_backend", "interact(n, d, K)")
    return BackendConfig.create(kind, precision).interact(
        n, d, K, block_users=block_users, interpret=interpret)


def get_graph_backend(
    n_rows: int,
    n_cols: int | None = None,
    kind: str | None = None,
    *,
    row_block: int = 256,
    interpret: bool | None = None,
) -> GraphBackend:
    """Deprecated — use ``BackendConfig.create(kind).graph``."""
    _deprecated("get_graph_backend", "graph(n_rows, n_cols)")
    return BackendConfig.create(kind).graph(
        n_rows, n_cols, row_block=row_block, interpret=interpret)


def get_retrieval_backend(
    d: int,
    K_short: int,
    kind: str | None = None,
    *,
    block_users: int = 128,
    block_items: int = 512,
    row_block: int = 8,
    item_block: int = 4096,
    interpret: bool | None = None,
    precision=None,
) -> RetrievalBackend:
    """Deprecated — use ``BackendConfig.create(kind, precision).retrieval``."""
    _deprecated("get_retrieval_backend", "retrieval(d, K_short)")
    return BackendConfig.create(kind, precision).retrieval(
        d, K_short, block_users=block_users, block_items=block_items,
        row_block=row_block, item_block=item_block, interpret=interpret)
