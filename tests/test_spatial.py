"""Plane-parallel execution (``core.spatial``): geometry/verdict unit tests
in-process, oracle parity + jaxpr collective proofs in a forced-8-device
subprocess (the ``test_distributed.py`` pattern — the XLA host-device flag
must be set before jax initializes)."""
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

from repro.core import spatial
from repro.core.autotune import (candidate_routes, route_from_json,
                                 route_to_json, spec_key, _measurable)
from repro.core.plan import ConvSpec, plan_conv

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))


def dilated385(spatial_tiles=(4, 1), c=4, n=4):
    """The ISSUE's 385x385 dilated-context geometry (channel count scaled
    down for test wall-clock; the tiling algebra only sees H/W/k/s/d)."""
    return ConvSpec(kind="dilated", in_hw=(385, 385), in_c=c, out_c=n,
                    kernel_hw=(3, 3), strides=(1, 1),
                    padding=((2, 2), (2, 2)), dilation=(2, 2),
                    backend="xla", spatial=spatial_tiles)


def decoder96(spatial_tiles=(2, 2), c=16, n=16):
    """The large transposed-decoder geometry (k4 s2, zoo 'SAME' padding)."""
    return ConvSpec(kind="transposed", in_hw=(96, 96), in_c=c, out_c=n,
                    kernel_hw=(4, 4), strides=(2, 2),
                    padding=((1, 3), (1, 3)), backend="xla",
                    spatial=spatial_tiles)


# ---------------------------------------------------------------------------
# geometry (pure arithmetic, no devices)
# ---------------------------------------------------------------------------

def test_single_dim_geometry():
    sp = spatial.spatial_plan(dilated385((4, 1)))
    th, tw = sp.dims
    assert (th.dev, tw.dev) == (4, 1)
    assert th.pad_to == th.block * 4 and th.pad_to >= th.size
    # slab = strided span of the block's outputs + dilated kernel reach
    t = th.out_pad // 4
    assert th.tin == (t - 1) * 1 + 2 * 2 + 1
    assert th.halo_lo == 2                       # == the spec's low pad
    assert th.halo_lo + th.block + th.halo_hi >= th.tin
    assert th.halo_lo <= th.block and th.halo_hi <= th.block
    # local spec: zero padding on the sharded dim (halo replaces it)
    assert sp.local_spec.padding[0] == (0, 0)
    assert sp.local_spec.spatial == (1, 1)
    assert sp.out_hw == (385, 385)


def test_transposed_dim_geometry():
    sp = spatial.spatial_plan(decoder96((2, 2)))
    for d in sp.dims:
        assert d.dev == 2 and d.pad_to == 96 and d.block == 48
        assert d.out_pad == 192
        assert d.halo_lo <= d.block and d.halo_hi <= d.block
    assert sp.out_hw == (192, 192)
    # the local plan must share the parent's superpack layout bit-for-bit
    parent = plan_conv(decoder96((1, 1)))
    local = plan_conv(sp.local_spec)
    assert local.total_taps == parent.total_taps


def test_infeasible_geometries_return_none():
    assert spatial.spatial_plan(dilated385((1, 1))) is None
    # block of 1 row cannot hold a k5 halo: one-hop exchange infeasible
    tiny = ConvSpec(kind="conv", in_hw=(16, 16), in_c=2, out_c=2,
                    kernel_hw=(5, 5), strides=(1, 1),
                    padding=((2, 2), (2, 2)), backend="xla",
                    spatial=(16, 1))
    assert spatial.spatial_plan(tiny) is None


# ---------------------------------------------------------------------------
# plan-layer verdict + serialization
# ---------------------------------------------------------------------------

def test_dev_verdict_emitted_above_bytes_floor():
    plan = plan_conv(dilated385((4, 1), c=32, n=32))
    assert plan.route_for_batch(4).dev_tiles == (4, 1)
    # path/tiles stay the single-device verdict — the fallback route
    ref = plan_conv(dilated385((1, 1), c=32, n=32))
    assert plan.route_for_batch(4).path == ref.route_for_batch(4).path


def test_dev_verdict_suppressed_below_bytes_floor():
    small = ConvSpec(kind="conv", in_hw=(32, 32), in_c=4, out_c=4,
                     kernel_hw=(3, 3), strides=(1, 1),
                     padding=((1, 1), (1, 1)), backend="xla",
                     spatial=(2, 1))
    plan = plan_conv(small)
    assert all(r.dev_tiles is None for r in plan.routes)


def test_route_json_roundtrip_and_spec_key():
    plan = plan_conv(dilated385((4, 1), c=32, n=32))
    r = plan.route_for_batch(4)
    assert r.dev_tiles == (4, 1)
    assert route_from_json(route_to_json(r)) == r
    assert spec_key(dilated385((4, 1))).endswith(":sp4x1")
    # unchanged spec -> unchanged key: old cache entries stay valid
    assert ":sp" not in spec_key(dilated385((1, 1)))


def test_autotune_candidates_pair_dev_and_single():
    plan = plan_conv(dilated385((4, 1), c=32, n=32))
    cands = candidate_routes(plan, 4)
    dev = [r for r in cands if r.dev_tiles == (4, 1)]
    single = [r for r in cands if r.dev_tiles is None]
    assert dev and single
    # a dev-tiled candidate is unmeasurable without a bound matching mesh
    assert not _measurable(dev[0])


def test_apply_falls_back_without_mesh():
    """A dev_tiles route on a mesh-less host must silently execute the
    single-device route and agree bit-for-bit."""
    import jax
    import jax.numpy as jnp
    spec = dilated385((4, 1), c=8, n=8)
    plan, ref = plan_conv(spec), plan_conv(dilated385((1, 1), c=8, n=8))
    assert plan.route_for_batch(1).dev_tiles == (4, 1)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (1, 385, 385, 8), jnp.float32)
    kern = jax.random.normal(k2, (3, 3, 8, 8), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(plan.apply(x, plan.pack(kern))),
        np.asarray(ref.apply(x, ref.pack(kern))))


# ---------------------------------------------------------------------------
# multi-device subprocess suite
# ---------------------------------------------------------------------------

def _capability() -> str | None:
    probe = (
        "import jax\n"
        "from jax.sharding import PartitionSpec as P\n"
        "from repro.launch.mesh import make_spatial_mesh\n"
        "m = make_spatial_mesh(2, 2)\n"
        "f = jax.shard_map(lambda x: x * 2, mesh=m, in_specs=P('sp_h'),\n"
        "                  out_specs=P('sp_h'))\n"
        "f(jax.numpy.ones((4,)))\n"
        "print(jax.device_count())\n")
    try:
        r = subprocess.run([sys.executable, "-c", probe], env=ENV,
                           capture_output=True, text=True, timeout=120)
    except Exception as e:  # noqa: BLE001 - any probe failure means skip
        return f"spatial mesh probe failed to run: {e}"
    if r.returncode != 0:
        tail = (r.stderr.strip().splitlines() or ["unknown error"])[-1]
        return f"spatial mesh unavailable: {tail}"
    if int(r.stdout.strip() or 0) < 8:
        return "need 8 forced host devices"
    return None


_SKIP = _capability()
multidev = pytest.mark.skipif(_SKIP is not None, reason=f"{_SKIP}")


def run_py(code: str, timeout=600):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=ENV, capture_output=True, text=True,
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


_PARITY_PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import spatial
from repro.core.plan import ConvSpec, plan_conv
from repro.launch.mesh import make_spatial_mesh

def parity(spec_kw, dev_tiles, batch=2, tol=2e-6):
    sharded = plan_conv(ConvSpec(backend='xla', spatial=dev_tiles, **spec_kw))
    single = plan_conv(ConvSpec(backend='xla', **spec_kw))
    assert sharded.route_for_batch(batch).dev_tiles == dev_tiles, \\
        sharded.route_for_batch(batch)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    h, w = spec_kw['in_hw']
    x = jax.random.normal(k1, (batch, h, w, spec_kw['in_c']), jnp.float32)
    kern = jax.random.normal(
        k2, spec_kw['kernel_hw'] + (spec_kw['in_c'], spec_kw['out_c']),
        jnp.float32)
    pk = single.pack(kern)

    def loss(plan):
        return lambda x, pk: jnp.sum(plan.apply(x, pk) ** 2)

    y1 = single.apply(x, pk)
    g1x, g1k = jax.grad(loss(single), argnums=(0, 1))(x, pk)
    mesh = make_spatial_mesh(*dev_tiles)
    with spatial.use_spatial_mesh(mesh):
        yd = jax.jit(lambda x, pk: sharded.apply(x, pk))(x, pk)
        gdx, gdk = jax.jit(jax.grad(loss(sharded), argnums=(0, 1)))(x, pk)
    for a, b in ((y1, yd), (g1x, gdx), (g1k, gdk)):
        err = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-30))
        assert err < tol, err
    return yd
"""


@multidev
def test_parity_dilated_context_385():
    run_py(_PARITY_PRELUDE + """
    parity(dict(kind='dilated', in_hw=(385, 385), in_c=4, out_c=4,
                kernel_hw=(3, 3), strides=(1, 1), padding=((2, 2), (2, 2)),
                dilation=(2, 2)), (4, 1))
    print('dilated385 fwd+vjp parity OK')
    """)


@multidev
def test_parity_transposed_decoder_2x2():
    run_py(_PARITY_PRELUDE + """
    parity(dict(kind='transposed', in_hw=(96, 96), in_c=16, out_c=16,
                kernel_hw=(4, 4), strides=(2, 2), padding=((1, 3), (1, 3))),
           (2, 2))
    print('decoder96 2x2 fwd+vjp parity OK')
    """)


@multidev
def test_parity_strided_conv():
    run_py(_PARITY_PRELUDE + """
    parity(dict(kind='conv', in_hw=(385, 385), in_c=4, out_c=4,
                kernel_hw=(3, 3), strides=(2, 2), padding=((1, 1), (1, 1))),
           (2, 1))
    print('strided conv parity OK')
    """)


@multidev
def test_halo_exchange_is_collective_permute():
    """The ISSUE's lowering proof: the sharded program moves halos with
    ppermute (collective-permute) and NEVER all-gathers the plane —
    forward and backward both."""
    run_py("""
    import jax, jax.numpy as jnp
    from repro.core import spatial
    from repro.core.plan import ConvSpec, plan_conv
    from repro.launch.mesh import make_spatial_mesh

    spec = ConvSpec(kind='dilated', in_hw=(385, 385), in_c=4, out_c=4,
                    kernel_hw=(3, 3), strides=(1, 1),
                    padding=((2, 2), (2, 2)), dilation=(2, 2),
                    backend='xla', spatial=(4, 1))
    plan = plan_conv(spec)
    x = jnp.zeros((2, 385, 385, 4))
    pk = jnp.zeros((plan.total_taps * 4, 4))
    mesh = make_spatial_mesh(4, 1)
    with spatial.use_spatial_mesh(mesh):
        fwd = str(jax.make_jaxpr(lambda a, k: plan.apply(a, k))(x, pk))
        bwd = str(jax.make_jaxpr(jax.grad(
            lambda a, k: jnp.sum(plan.apply(a, k) ** 2),
            argnums=(0, 1)))(x, pk))
    assert fwd.count('ppermute') >= 1, fwd.count('ppermute')
    assert 'all_gather' not in fwd
    assert bwd.count('ppermute') >= 1
    assert 'all_gather' not in bwd
    print('collective-permute lowering proof OK')
    """)


@multidev
def test_shard_params_nondivisible_warns_once():
    run_py("""
    import warnings
    import jax.numpy as jnp
    from repro.layers import common as cm
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import DistContext

    dist = DistContext(mesh=make_host_mesh(data=2, model=2))
    p = {'head': jnp.ones((3, 8))}          # 3 does not divide model=2
    s = {'head': cm.spec('model', None)}
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter('always')
        out = dist.shard_params(p, s)
        hits = [x for x in w if 'shard_params' in str(x.message)]
    assert len(hits) == 1, [str(x.message) for x in w]
    msg = str(hits[0].message)
    assert 'head' in msg and 'dim 0' in msg and 'model' in msg, msg
    # replicated on the offending dim, no crash
    assert out['head'].shape == (3, 8)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter('always')
        dist.shard_params(p, s)             # same param: warned already
        assert not [x for x in w if 'shard_params' in str(x.message)]
    print('shard_params replication warning OK')
    """)


@multidev
def test_degrade_replans_spatial_tiles():
    """Serving integration: a spatially-sharded model serves behind the
    same admission layer, and ``degrade(spatial_tiles=...)`` re-plans
    ``dev_tiles`` on the shrunk mesh — outputs stay equal to the
    single-device closure."""
    run_py("""
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import spatial
    from repro.core.plan import ConvSpec, plan_conv
    from repro.serving.control_plane import ControlPlane, ServeRequest

    kw = dict(kind='dilated', in_hw=(385, 385), in_c=4, out_c=4,
              kernel_hw=(3, 3), strides=(1, 1), padding=((2, 2), (2, 2)),
              dilation=(2, 2), backend='xla')
    kern = jax.random.normal(jax.random.PRNGKey(0), (3, 3, 4, 4))

    def serve_for(tiles):
        plan = plan_conv(ConvSpec(spatial=tiles, **kw))
        pk = plan.pack(kern)
        return lambda x: plan.apply(x, pk)

    cp = ControlPlane()
    cp.register_image_model('seg', serve_for((1, 1)),
                            np.zeros((385, 385, 4), np.float32),
                            buckets=(1, 2))
    zs = [np.random.RandomState(i).randn(385, 385, 4).astype(np.float32)
          for i in range(2)]
    cp.run([ServeRequest(rid=i, model='seg', payload=z)
            for i, z in enumerate(zs)])
    before = {r.rid: r.out for r in cp.done}

    mesh = cp.degrade(8, spatial_tiles=(2, 2),
                      serve_fns={'seg': serve_for((2, 2))})
    assert dict(mesh.shape) == {'data': 2, 'sp_h': 2, 'sp_w': 2}
    assert cp.degraded['spatial_tiles'] == (2, 2)
    assert spatial.active_spatial_mesh()[0] is mesh
    cp.run([ServeRequest(rid=10 + i, model='seg', payload=z)
            for i, z in enumerate(zs)])
    after = {r.rid: r.out for r in cp.done}
    for i in range(2):
        np.testing.assert_allclose(after[10 + i], before[i],
                                   rtol=1e-4, atol=1e-5)
    print('spatial degrade re-plan OK')
    """)


# ---------------------------------------------------------------------------
# infeasible-tiling warning: named, once per spec, fallback untouched
# ---------------------------------------------------------------------------

def test_infeasible_tiling_warns_once_and_falls_back_bit_equal():
    """A transposed spec with non-uniform phases that *requests* device
    tiling must not silently plan single-device: a RuntimeWarning names
    the spec and the reason, exactly once per process — surviving
    ``plan_cache_clear()`` — and the fallback plan's output is bit-equal
    to the ``spatial=(1, 1)`` twin (the verdict vanishes, the math
    doesn't)."""
    import jax
    import jax.numpy as jnp
    from repro.core.plan import plan_cache_clear

    def spec(tiles):
        # k=3 s=2: phases carry 2 and 1 taps -> no uniform block tiling
        return ConvSpec(kind="transposed", in_hw=(24, 24), in_c=6, out_c=10,
                        kernel_hw=(3, 3), strides=(2, 2),
                        padding=((1, 0), (1, 0)), backend="xla",
                        spatial=tiles)

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        plan = plan_conv(spec((2, 2)))
        plan_cache_clear()              # re-derives the geometry...
        plan2 = plan_conv(spec((2, 2)))
    hits = [w for w in rec if issubclass(w.category, RuntimeWarning)
            and "spatial_plan" in str(w.message)]
    assert len(hits) == 1, [str(w.message) for w in rec]   # ...but warns once
    msg = str(hits[0].message)
    assert "spatial=(2, 2)" in msg and "transposed" in msg
    assert "non-uniform" in msg and "planning single-device" in msg

    assert all(r.dev_tiles is None for r in plan.routes)
    twin = plan_conv(spec((1, 1)))
    k = jax.random.normal(jax.random.PRNGKey(0), (3, 3, 6, 10), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 24, 6), jnp.float32)
    got = np.asarray(plan2.apply(x, plan2.pack(k)))
    want = np.asarray(twin.apply(x, twin.pack(k)))
    np.testing.assert_array_equal(got, want)    # bit-equal, not just close
