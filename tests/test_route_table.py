"""Golden route-table regression: every model-zoo conv site's per-bucket
execution route is pinned to ``tests/fixtures/route_table.json``.

A route is the engine's whole performance story for a site (Pallas vs XLA,
whole-plane vs spatially tiled, fused vs per-tap backward) — this test
turns any change to it into an **explicit fixture diff** instead of a
silent perf cliff.  After an intentional routing change::

    PYTHONPATH=src python tools/gen_route_table.py

and commit the regenerated fixture; the diff *is* the review artifact.
"""
import json
import pathlib

from tools.gen_route_table import FIXTURE, build_route_table


def _fmt(entry):
    routes = ", ".join(
        f"B{r['batch']}:{r['path']}"
        + (f"@sp{tuple(r['sp_tiles'])}" if r["sp_tiles"] else "")
        for r in entry["routes"])
    return f"{entry['name']}[{entry['backend']}] -> {routes}"


def test_route_table_matches_fixture():
    assert FIXTURE.exists(), \
        "fixture missing — run PYTHONPATH=src python tools/gen_route_table.py"
    want = json.loads(pathlib.Path(FIXTURE).read_text())
    got = build_route_table()
    if got == want:
        return
    want_by_key = {(e["name"], e["backend"]): e for e in want["entries"]}
    got_by_key = {(e["name"], e["backend"]): e for e in got["entries"]}
    lines = []
    for key in sorted(set(want_by_key) | set(got_by_key)):
        w, g = want_by_key.get(key), got_by_key.get(key)
        if w == g:
            continue
        lines.append(f"  was: {_fmt(w) if w else '<absent>'}")
        lines.append(f"  now: {_fmt(g) if g else '<absent>'}")
    raise AssertionError(
        "route table drifted from the golden fixture — if intentional, "
        "regenerate with `PYTHONPATH=src python tools/gen_route_table.py` "
        "and commit the diff:\n" + "\n".join(lines))


def test_fixture_records_the_reclaimed_geometry():
    """The acceptance-criterion geometry is pinned in the fixture: the
    385x385 atrous layer routes 'taps' on the XLA backend (what HEAD's
    pallas verdict also fell back to) and 'pallas' with spatial tiles on
    the Pallas backend, at every bucket including B=64."""
    table = json.loads(pathlib.Path(FIXTURE).read_text())
    by_key = {(e["name"], e["backend"]): e for e in table["entries"]}
    name = "dilated_bench_L9_385x385x32_d2"
    xla = by_key[(name, "xla")]
    pallas = by_key[(name, "pallas")]
    assert all(r["path"] == "taps" for r in xla["routes"])
    assert all(r["path"] == "pallas" and r["sp_tiles"]
               for r in pallas["routes"])


def test_every_pallas_verdict_has_lane_aligned_tiles():
    """Mosaic lays a channel tile out only when it is a multiple of the
    128-lane tile or the whole dim; its strided loads and stores take at
    most one lane tile, and the tiled kernels' halo DMA needs a lane-dense
    channel dim.  So every 'pallas' row of the fixture carries
    ``C_t, N_t ∈ {128} ∪ {the dim itself, when narrower}`` — with
    ``C_t == 128`` on the spatially tiled kernels."""
    table = json.loads(pathlib.Path(FIXTURE).read_text())
    n_checked = 0
    for e in table["entries"]:
        c, n = e["spec"]["in_c"], e["spec"]["out_c"]
        for r in e["routes"]:
            if r["path"] != "pallas":
                continue
            c_t, n_t = r["tiles"]
            assert n_t == min(n, 128), (e["name"], r)
            want_c = 128 if r["sp_tiles"] else min(c, 128)
            assert c_t == want_c, (e["name"], r)
            n_checked += 1
    assert n_checked > 0


def test_dcgan_batch_tiles_follow_the_vmem_rule():
    """The fused deconv's ``B_t`` is the largest divisor of the bucket whose
    working set fits the VMEM budget: 64 / 32 / 8 / 2 images per grid step
    for the Table-1 generator's dc0-dc3 at B64, one at B1 (the per-image
    grid), and every chosen working set within the budget."""
    import dataclasses

    import repro.core.plan as planmod
    from repro.kernels.untangled_conv import vmem_bytes_estimate_fused
    from repro.models import gan

    plans = gan.generator_plans(dataclasses.replace(gan.DCGAN,
                                                    backend="pallas"))
    assert [p.route_for_batch(64).b_tile for p in plans] == [64, 32, 8, 2]
    for plan in plans:
        (glh, ghh), (glw, ghw) = plan.gpad
        hg = plan.spec.in_hw[0] + glh + ghh
        wg = plan.spec.in_hw[1] + glw + ghw
        tap_rows = max(ex.out_hw[0] * ex.out_hw[1] for ex in plan.phases)

        def working_set(route, b_tile):
            return vmem_bytes_estimate_fused(
                hg, wg, route.tiles[0], plan.total_taps, route.tiles[1],
                plan.sum_uv, *plan.out_hw, tap_rows, b_tile=b_tile)

        assert plan.route_for_batch(1).b_tile == 1
        for route in plan.routes:
            assert route.path == "pallas" and route.batch % route.b_tile == 0
            assert working_set(route, route.b_tile) <= planmod._VMEM_BUDGET
            # the next divisor of the bucket up would not fit
            bigger = [d for d in range(route.b_tile + 1, route.batch + 1)
                      if route.batch % d == 0]
            if bigger:
                assert working_set(route, bigger[0]) > planmod._VMEM_BUDGET


def test_fixture_routes_carry_batch_tiles():
    """Only the whole-plane fused transposed kernel blocks the batch: every
    other route of the fixture, and every B1 route, runs one image per
    grid step."""
    table = json.loads(pathlib.Path(FIXTURE).read_text())
    blocked = 0
    for e in table["entries"]:
        for r in e["routes"]:
            fused = (e["spec"]["kind"] == "transposed"
                     and r["path"] == "pallas" and not r["sp_tiles"])
            if not fused or r["batch"] == 1:
                assert r["b_tile"] == 1, (e["name"], r)
            else:
                assert r["batch"] % r["b_tile"] == 0, (e["name"], r)
                blocked += r["b_tile"] > 1
    assert blocked > 0
