"""Multi-device integration tests (subprocess with forced host devices):
MoE expert-parallel == dense oracle; sharded train with failure/restart;
elastic restore onto a different mesh."""
import os
import subprocess
import sys
import textwrap

import pytest

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=4",
           PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))


def _mesh_capability() -> str | None:
    """Probe (in the same forced-device subprocess the tests use) whether the
    host can build the 2x2 mesh these tests need.  Returns a skip reason, or
    None when the prerequisites are met."""
    probe = (
        "import jax\n"
        "from jax.sharding import PartitionSpec as P\n"
        "from repro.launch.mesh import make_host_mesh\n"
        "m = make_host_mesh(data=2, model=2)\n"
        "f = jax.shard_map(lambda x: x * 2, mesh=m, in_specs=P('data'),\n"
        "                  out_specs=P('data'))\n"
        "f(jax.numpy.ones((4,)))\n"
        "print(len(list(m.devices.flat)))\n")
    try:
        r = subprocess.run([sys.executable, "-c", probe], env=ENV,
                           capture_output=True, text=True, timeout=120)
    except Exception as e:  # noqa: BLE001 - any probe failure means skip
        return f"mesh probe failed to run: {e}"
    if r.returncode != 0:
        tail = (r.stderr.strip().splitlines() or ["unknown error"])[-1]
        return f"host mesh unavailable: {tail}"
    n = int(r.stdout.strip() or 0)
    if n < 4:
        return f"need a 2x2 host mesh, got {n} device(s)"
    return None


_SKIP_REASON = _mesh_capability()
pytestmark = pytest.mark.skipif(
    _SKIP_REASON is not None,
    reason=f"distributed prerequisites not met: {_SKIP_REASON}")


def run_py(code: str, timeout=600):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=ENV, capture_output=True, text=True,
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_moe_ep_matches_dense_oracle():
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import registry
    from repro.layers import moe as moe_lib
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import DistContext, DEFAULT_RULES

    cfg = registry.get_reduced('dbrx-132b')
    # capacity_factor = E/k guarantees no dropped token -> exact equality
    cfg = dataclasses.replace(cfg, moe_impl='ep',
                              capacity_factor=cfg.n_experts / cfg.top_k)
    key = jax.random.PRNGKey(0)
    p, _ = moe_lib.moe_init(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model),
                          jnp.bfloat16)
    mesh = make_host_mesh(data=2, model=2)
    rules = dict(DEFAULT_RULES); rules['batch'] = 'data'
    dist = DistContext(mesh=mesh, rules=rules)
    with mesh:
        y_ep = jax.jit(lambda p, x: moe_lib.moe_apply_ep(p, x, cfg, dist))(p, x)
    y_dense = moe_lib.moe_apply_dense(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y_ep, np.float32),
                               np.asarray(y_dense, np.float32),
                               rtol=5e-2, atol=5e-2)
    print('EP == dense oracle OK')
    """)


def test_moe_a2a_ep_matches_dense_oracle():
    """All-to-all EP (1 expert/chip over data*model) == dense oracle,
    including the padded-token decode path."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs import registry
    from repro.layers import moe as moe_lib
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import DistContext, DEFAULT_RULES

    cfg = registry.get_reduced('dbrx-132b')
    cfg = dataclasses.replace(cfg, moe_impl='ep',
                              capacity_factor=cfg.n_experts / cfg.top_k * 4)
    key = jax.random.PRNGKey(0)
    p, _ = moe_lib.moe_init(key, cfg)
    mesh = make_host_mesh(data=2, model=2)
    rules = dict(DEFAULT_RULES)
    rules['batch'] = 'data'
    rules['expert'] = ('data', 'model')       # 4 experts over 4 chips
    dist = DistContext(mesh=mesh, rules=rules)
    for (b, s) in ((4, 8), (2, 3)):           # divisible and PADDED cases
        x = jax.random.normal(jax.random.PRNGKey(b), (b, s, cfg.d_model),
                              jnp.bfloat16)
        with mesh:
            y = jax.jit(lambda p, x: moe_lib.moe_apply_ep_a2a(
                p, x, cfg, dist))(p, x)
        y_ref = moe_lib.moe_apply_dense(p, x, cfg)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32),
                                   rtol=5e-2, atol=5e-2)
    print('a2a EP == dense oracle OK (incl. padding)')
    """)


def test_sharded_train_with_failure_restart(tmp_path):
    out = run_py(f"""
    import numpy as np
    from repro.launch.train import train
    losses, final = train('llama3.2-1b', reduced=True, steps=12, batch=8,
                          seq=32, ckpt_dir={str(tmp_path)!r}, ckpt_every=4,
                          fail_at=[6], data=2, model=2)
    # the claim under test is fault tolerance: the injected failure at step 6
    # must be survived via checkpoint restore and training must complete.
    assert final == 12, final
    assert np.isfinite(losses).all()
    # random-token loss barely moves in 12 steps; just bound the drift
    assert losses[-1] < losses[0] + 0.1, (losses[0], losses[-1])
    print('sharded train with restart OK', losses[0], losses[-1])
    """)
    assert "restart" in out or "OK" in out


def test_elastic_restore_on_smaller_mesh(tmp_path):
    run_py(f"""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.elastic import restore_on_mesh, shrink_mesh
    from repro.sharding import DistContext, DEFAULT_RULES
    from repro.train.checkpoint import CheckpointManager

    state = {{'w': jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
    specs = {{'w': P(None, 'model')}}
    big = make_host_mesh(data=2, model=2)
    ck = CheckpointManager({str(tmp_path)!r}, async_save=False)
    ck.save(3, state)

    small = shrink_mesh(2, model=2)      # lost half the chips
    assert dict(zip(small.axis_names, small.devices.shape)) == \\
        {{'data': 1, 'model': 2}}
    dist = DistContext(mesh=small, rules=dict(DEFAULT_RULES))
    restored = restore_on_mesh(ck, state, specs, dist)
    np.testing.assert_array_equal(np.asarray(restored['w']),
                                  np.arange(64).reshape(8, 8))
    shd = restored['w'].sharding
    assert shd.spec == P(None, 'model'), shd
    print('elastic restore OK')
    """)


def test_crosspod_compressed_allreduce():
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import compress
    mesh = make_host_mesh(data=2, model=1, pod=2)
    grads = {'w': jnp.stack([jnp.full((4,), float(i)) for i in range(2)])}
    errs = {'w': jnp.zeros((2, 4))}

    def f(g, e):
        return compress.crosspod_allreduce_compressed(g, e, 'pod')

    fm = jax.shard_map(f, mesh=mesh,
                       in_specs=({'w': P('pod', None)},) * 2,
                       out_specs=({'w': P('pod', None)},) * 2,
                       check_vma=False)
    with mesh:
        mean, new_e = fm(grads, errs)
    # mean over pods of [0, 1] = 0.5 everywhere
    np.testing.assert_allclose(np.asarray(mean['w']), 0.5, atol=0.01)
    print('compressed cross-pod allreduce OK')
    """)
