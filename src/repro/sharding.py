"""Sharding rules: logical param axes -> mesh axes, activation constraints.

The model code annotates parameters with *logical* axis names ("heads",
"ffn", "vocab", "expert", ...).  ``DistContext`` owns the mapping from
logical axes to physical mesh axes — changing a parallelism strategy (the
§Perf hillclimb lever) means editing RULES, not models.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# default logical->mesh translation (megatron TP on 'model', experts EP'd)
DEFAULT_RULES: dict[str, Any] = {
    "heads": "model",
    "kv_heads": "model",         # cleared when num_kv_heads % TP != 0
    "ffn": "model",
    "vocab": "model",
    "expert": "model",
    "expert_ffn": None,
    "batch": ("data",),          # overridden to ('pod','data') multi-pod
    "seq": None,                 # set to 'model' to turn on SP residuals
    "kv_seq": None,              # decode cache sequence dim (long-context)
    # superpacked conv weights (core.plan): one tap-major (ΣT·C, N) buffer
    # per site.  Row dim mixes taps and input channels (plan-time offsets
    # index into it), so the default shards only the out-channel dim —
    # flip "conv_taps" to 'model' for row-parallel superpacks instead.
    "conv_taps": None,
    "conv_out": "model",
    # plane-parallel execution (core.spatial): one conv plane's spatial
    # dims sharded over the mesh, halo exchange at tile boundaries.  The
    # logical axes name the *image* rows/cols; ``make_spatial_mesh``
    # provides the physical 'sp_h'/'sp_w' axes.
    "plane_h": "sp_h",
    "plane_w": "sp_w",
}

# logical spec of every superpacked conv weight buffer
SUPERPACK_SPEC = P("conv_taps", "conv_out")

# logical spec of a plane-parallel (B, H, W, C) activation
PLANE_SPEC = P("batch", "plane_h", "plane_w")


# (param-path, axis) pairs already warned about by ``shard_params`` — the
# best-effort replication fallback is silent-by-design per call site, but
# the *first* hit for a given param deserves a visible trace.
_REPLICATION_WARNED: set = set()


@dataclasses.dataclass(frozen=True)
class DistContext:
    mesh: Mesh
    rules: dict = dataclasses.field(default_factory=lambda: dict(DEFAULT_RULES))

    @property
    def batch_axes(self):
        return self.rules["batch"]

    @property
    def model_axis(self):
        return "model"

    def resolve(self, spec: P) -> P:
        """Translate a logical PartitionSpec into a mesh PartitionSpec."""
        out = []
        for ax in spec:
            if ax is None:
                out.append(None)
            elif isinstance(ax, str) and ax in self.rules:
                out.append(self.rules[ax])
            else:
                out.append(ax)
        return P(*out)

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, self.resolve(spec))

    def param_shardings(self, specs_tree):
        return jax.tree.map(
            lambda sp: self.sharding(sp), specs_tree,
            is_leaf=lambda x: isinstance(x, P))

    # ---- activation constraints -------------------------------------------
    def act_spec(self, *, seq_dim: bool = True) -> P:
        """(B, S, D) residual-stream spec: batch over DP axes, optional SP."""
        if seq_dim:
            return P(self.rules["batch"], self.rules["seq"], None)
        return P(self.rules["batch"], None)

    def image_spec(self) -> P:
        """(B, H, W, C) image/latent batch spec: data-parallel over the
        batch dim, spatial/channel replicated (trailing dims implicit)."""
        return P(self.rules["batch"])

    def plane_spec(self) -> P:
        """(B, H, W, C) plane-parallel spec: batch over DP axes, the plane's
        rows/cols over the spatial mesh axes (``core.spatial`` executor)."""
        return self.resolve(PLANE_SPEC)

    def spatial_tiles(self) -> tuple[int, int]:
        """(D_h, D_w) device-tiling extents this mesh offers a conv plane:
        the sizes of the mesh axes the 'plane_h'/'plane_w' logical axes
        resolve to (1 when unmapped or absent from the mesh) — what model
        configs feed into ``ConvSpec.spatial``."""
        if self.mesh is None:
            return (1, 1)
        out = []
        for logical in ("plane_h", "plane_w"):
            ax = self.rules.get(logical)
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                if a is not None and a in self.mesh.shape:
                    n *= int(self.mesh.shape[a])
            out.append(n)
        return tuple(out)

    def shard_params(self, params, specs):
        """Place a param tree onto the mesh per its logical spec tree — the
        DistContext-aware half of every planned model's ``*_init``.  A dim
        whose size doesn't divide its mesh axes replicates instead (the
        same rule as ``kv_heads``: sharding is best-effort, never a crash —
        e.g. a 3-channel image head stays replicated under TP=2).  Like
        ``constrain``, a mesh-less context is a no-op."""
        if self.mesh is None:
            return params
        from repro.core.plan import QuantizedSuperpack

        def put(path, p, sp):
            if isinstance(p, QuantizedSuperpack):
                # quantized superpack: the int8 codes shard exactly like the
                # dense buffer; the (rows, 1) scale column follows the row
                # axis only (its singleton N dim is never split)
                row_sp = P(*tuple(sp)[:1])
                return QuantizedSuperpack(put(path, p.q, sp),
                                          put(path, p.scale, row_sp))
            resolved = tuple(self.resolve(sp))
            resolved += (None,) * (len(p.shape) - len(resolved))
            out = []
            for i, (dim, ax) in enumerate(zip(p.shape, resolved)):
                if ax is None:
                    out.append(None)
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                n = 1
                for a in axes:
                    n *= int(self.mesh.shape[a])
                if dim % n:
                    name = jax.tree_util.keystr(path)
                    if (name, i, ax) not in _REPLICATION_WARNED:
                        _REPLICATION_WARNED.add((name, i, ax))
                        warnings.warn(
                            f"shard_params: param {name} dim {i} (size "
                            f"{dim}) does not divide mesh axis {ax!r} "
                            f"(extent {n}) — replicating that dim instead",
                            RuntimeWarning, stacklevel=2)
                    out.append(None)
                    continue
                out.append(ax)
            return jax.device_put(p, NamedSharding(self.mesh, P(*out)))

        return jax.tree_util.tree_map_with_path(
            put, params, specs,
            is_leaf=lambda x: isinstance(x, QuantizedSuperpack))

    def constrain(self, x, spec: Optional[P] = None):
        if self.mesh is None:
            return x
        spec = spec if spec is not None else self.act_spec()
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.resolve(spec)))


def single_device_dist() -> Optional[DistContext]:
    """None-context for smoke tests (no mesh, constraints are no-ops)."""
    return None


def stack_specs(specs_tree, n_lead: int = 1):
    """Prepend ``n_lead`` None axes to every PartitionSpec (stacked stages)."""
    return jax.tree.map(
        lambda sp: P(*((None,) * n_lead + tuple(sp))), specs_tree,
        is_leaf=lambda x: isinstance(x, P))
