"""Logical-axis sharding rules (MaxText-style) for pjit/GSPMD.

Model code annotates activations/params with *logical* axis names
(``batch``, ``heads``, ``ffn`` ...).  A :class:`ShardingRules` table maps
logical names onto physical mesh axes; the launcher installs the mesh and
rules for the duration of a step function.  Outside any mesh context all
annotations are no-ops, so the same model code runs in single-device smoke
tests and 512-chip dry-runs.

Parallelism encoded by the default rules:
  * DP: ``batch`` over ("pod", "data")
  * TP: ``heads`` / ``kv_heads`` / ``ffn`` / ``vocab`` over "model"
    (Megatron column/row pairs emerge from GSPMD on the matmul chains)
  * EP: ``experts`` over "data" with expert FFN dim over "model"
    (dispatch reshard = GSPMD all-to-all)
  * SP: ``kv_seq`` over "data" for long-context decode (flash-decode style
    partial-softmax combine inserted by GSPMD on the reduction)
  * ZeRO-1: optimizer-state ``fsdp`` axis over ("data",)
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# every shard_map user in the repo goes through this one spelling: the
# bodies hold Pallas calls, whose outputs carry no varying-axis types
shard_map_compat = functools.partial(jax.shard_map, check_vma=False)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Mapping logical axis name -> mesh axis (str/tuple) or None."""

    rules: dict

    def spec(self, *names: str | None) -> P:
        axes = []
        used: set[str] = set()
        for nm in names:
            if nm is None:
                axes.append(None)
                continue
            ax = self.rules.get(nm)
            members = set(ax) if isinstance(ax, tuple) else {ax}
            # a mesh axis may appear at most once in a PartitionSpec;
            # earlier logical names win (e.g. batch over kv_seq on "data")
            if ax is None or (members & used):
                axes.append(None)
            else:
                axes.append(ax)
                used |= members
        return P(*axes)


def default_rules(multi_pod: bool = False) -> ShardingRules:
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    return ShardingRules(rules={
        "batch": batch_axes,
        "expert_group": batch_axes,
        "seq": None,
        "kv_seq": "data",          # long-context decode: shard cache length
        "embed": None,
        "mlp_embed": None,
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "ffn": "model",
        "vocab": "model",
        "experts": "data",
        "expert_ffn": "model",
        "ssm_heads": "model",
        "ssm_state": None,
        "conv_dim": "model",
        "tp": "model",             # generic TP annotation (e.g. MoE out D)
        "layers": None,
        "fsdp": "data",            # optimizer-state (ZeRO-1) sharding axis
        "stage": "pod",            # pipeline stages (optional profile)
    })


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Mesh | None = None
        self.rules: ShardingRules | None = None


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh_and_rules(mesh: Mesh, rules: ShardingRules):
    """Install mesh + rules; model annotations become real constraints.

    No ambient-mesh context is required: ``constrain`` builds explicit
    NamedShardings, which carry the mesh into the jaxpr on their own.
    """
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def set_rules(rules: ShardingRules):
    _CTX.rules = rules


def active_mesh() -> Mesh | None:
    return _CTX.mesh


def logical_spec(*names: str | None) -> P:
    rules = _CTX.rules
    if rules is None:
        return P(*([None] * len(names)))
    return rules.spec(*names)


def constrain(x: jax.Array, *names: str | None) -> jax.Array:
    """Annotate ``x`` with logical axes; no-op without an active mesh."""
    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None or rules is None:
        return x
    if x.ndim != len(names):
        raise ValueError(f"rank {x.ndim} vs {len(names)} logical names {names}")
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, rules.spec(*names)))


def named_sharding(mesh: Mesh, rules: ShardingRules, *names) -> NamedSharding:
    return NamedSharding(mesh, rules.spec(*names))


# ---------------------------------------------------------------------------
# Tile-grid scale-out: (tile-row x batch) sharding specs for the megakernel
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TileGridShardSpecs:
    """PartitionSpecs of every tile-grid megakernel operand/output class.

    The tile-grid kernel's pallas grid is (tile rows x batch blocks); the
    distributed layout shards the *work* over both mesh axes:

      * ``coef`` — the ``[L, To, Ti, C, P, 8]`` coefficient stacks (and
        the ``[L, C]`` parity tables / ``[L, To, Ti, P, 12]`` gains) of
        the deep-grid layout: REPLICATED.  Each device slices
        its own tile-row slab (axis 1) in-body (``axis_index`` over the
        row axis).  They are small, and feeding them row-partitioned
        trips a GSPMD mis-partitioning bug on this jax version when the
        stacks are traced (built by concatenate under an enclosing jit,
        e.g. ``jit(grad(...))`` over unpacked tiles) — see the note in
        ``repro.kernels.ops``;
      * ``x_plane`` — the ``[Ti, P, B]`` input planes (batch on lanes):
        batch-split, replicated over tile rows (every row sweeps the
        whole input);
      * ``o_plane`` — the ``[To, P, B]`` combined row outputs: split on
        both axes (each device owns its rows' outputs for its batch);
      * ``stage`` — the ``[L, To, Ti, P, B]`` VJP stage residuals (the
        kernel's tile-major layout): batch and tile rows both split,
        layer, input-tile and pair-slot axes whole;
      * ``dx_plane`` — the ``[Ti, P, B]`` input cotangent *after* the
        cross-device ``psum`` over the row axis (the matched-line
        combiner's transpose): batch-split, replicated over rows.

    ``coef`` is also the out_spec of the VJP's coefficient grads: the
    backward psums them over the batch axis and all-gathers over the row
    axis, so they leave the shard_map replicated too.
    """

    coef: P
    x_plane: P
    o_plane: P
    stage: P
    dx_plane: P


def tile_grid_shard_specs(row_axis: str = "rows",
                          data_axis: str = "data") -> TileGridShardSpecs:
    """The canonical (tile-row x batch) sharding of the tile-grid kernel,
    whose planes carry the batch on lanes (``[.., P, B]``, data axis
    last)."""
    return TileGridShardSpecs(
        coef=P(),
        x_plane=P(None, None, data_axis),
        o_plane=P(row_axis, None, data_axis),
        stage=P(None, row_axis, None, None, data_axis),
        dx_plane=P(None, None, data_axis),
    )


# ---------------------------------------------------------------------------
# Data-parallel wrapper over the batch grid
# ---------------------------------------------------------------------------

def data_parallel(apply_fn, mesh: Mesh, *, axis_name: str = "data"):
    """Shard-map a batched ``apply_fn(params, x)`` over ``mesh[axis_name]``.

    Parameters are replicated; ``x`` is split on its leading (batch) axis;
    each device runs the *same* program — e.g. the fused RFNN network
    megakernel — on its batch shard, and outputs are re-concatenated along
    the batch axis.  Ragged batches are zero-padded up to a multiple of the
    axis size and sliced back, so any request count works (serving ticks
    don't have to align with the device count).
    """
    n_dev = mesh.shape[axis_name]
    # jit the shard_map: without it every call re-traces the body, and
    # trace-time tracers defeat the megakernel's coefficient-pack cache —
    # steady-state serving ticks must stay zero-packing-work when sharded
    fn = jax.jit(shard_map_compat(apply_fn, mesh=mesh,
                                  in_specs=(P(), P(axis_name)),
                                  out_specs=P(axis_name)))

    replicated = NamedSharding(mesh, P())

    def call(params, x):
        b = x.shape[0]
        pad = (-b) % n_dev
        if not pad:
            return fn(params, x)
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
        # a ragged slice of a batch-split array is undefined on a mesh
        # with explicit axes (``jax.make_mesh``): gather, then slice
        return jax.sharding.reshard(fn(params, x), replicated)[:b]

    return call
