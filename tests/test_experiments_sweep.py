"""Batched experiment-sweep subsystem: batched == serial equivalence, the
content-hash cache, grid expansion, and the benchmark CSV contract."""
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core.noc import FlattenedButterfly, Mesh2D, Torus2D, Torus3D
from repro.core.partition import powerlaw_partition, random_partition
from repro.core.placement import (
    Placement,
    auto_mesh_for_parts,
    greedy_placement,
    random_placement,
)
from repro.core.simulator import simulate
from repro.core.traffic import traffic_from_partition
from repro.experiments.batched import (
    batched_weighted_hops,
    routing_operator,
    simulate_batch,
    simulate_serial,
)
from repro.experiments.cache import SweepCache, graph_digest
from repro.experiments.grid import GRIDS, grid_by_name
from repro.experiments.sweep import figure_comparisons, run_sweep
from repro.graph.generators import rmat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _configs(n_graphs=3, parts=4, topology=None, seed=0):
    """(traffics, placements) for a mixed proposed/baseline batch."""
    topo = topology or auto_mesh_for_parts(parts)
    traffics, placements = [], []
    for i in range(n_graphs):
        g = rmat(120, 900, seed=seed + i)
        for part_fn, place_seed in ((powerlaw_partition, 0), (random_partition, i + 1)):
            p = part_fn(g.src, g.dst, g.num_nodes, parts)
            t = traffic_from_partition(p, g.src, g.dst)
            traffics.append(t)
            placements.append(random_placement(t.num_logical, topo, seed=place_seed))
    return traffics, placements


class TestBatchedEquivalence:
    @pytest.mark.parametrize("topology", ["mesh2d", "fbutterfly"])
    def test_numpy_backend_matches_serial_simulate(self, topology):
        parts = 4
        topo = auto_mesh_for_parts(parts, topology)
        traffics, placements = _configs(3, parts, topo)
        iters = np.arange(1, len(traffics) + 1)
        batched = simulate_batch(traffics, placements, num_iterations=iters, backend="numpy")
        for t, p, it, b in zip(traffics, placements, iters, batched):
            s = simulate(t, p, num_iterations=int(it))
            for field in (
                "exec_time_s", "energy_j", "avg_hops", "total_bytes", "byte_hops",
                "t_compute_s", "t_network_s", "t_serialization_s", "e_network_j",
                "e_compute_j",
            ):
                assert getattr(b, field) == pytest.approx(
                    getattr(s, field), rel=1e-12, abs=1e-30
                ), field

    def test_jax_backend_matches_serial_simulate(self):
        pytest.importorskip("jax")
        traffics, placements = _configs(2, 4)
        batched = simulate_batch(traffics, placements, num_iterations=3, backend="jax")
        for t, p, b in zip(traffics, placements, batched):
            s = simulate(t, p, num_iterations=3)
            # jax runs f32 on CPU by default — looser tolerance.
            assert b.exec_time_s == pytest.approx(s.exec_time_s, rel=1e-4)
            assert b.energy_j == pytest.approx(s.energy_j, rel=1e-4)
            assert b.avg_hops == pytest.approx(s.avg_hops, rel=1e-4)

    def test_torus3d_routes_exactly_and_matches_serial(self):
        # Torus3D now carries wrap-aware dimension-ordered routing, so the
        # batched path builds an exact operator instead of falling back.
        topo = Torus3D(2, 2, 4)
        assert routing_operator(topo) is not None
        g = rmat(80, 500, seed=1)
        p = powerlaw_partition(g.src, g.dst, g.num_nodes, 4)
        t = traffic_from_partition(p, g.src, g.dst)
        pl = random_placement(t.num_logical, topo, seed=0)
        (b,) = simulate_batch([t], [pl], backend="numpy")
        s = simulate(t, pl)
        assert b.exec_time_s == pytest.approx(s.exec_time_s, rel=1e-12)
        assert b.t_serialization_s == pytest.approx(s.t_serialization_s, rel=1e-12)

    def test_routeless_topology_uses_serial_fallback(self):
        # The uniform-spread fallback stays covered via a stub topology with
        # no routing model (batched and serial must agree on it too).
        class NoRoute(Torus3D):
            def route_links_ordered(self, c0, c1, order):
                return None

        topo = NoRoute(2, 2, 4, name="noroute3d")
        assert routing_operator(topo) is None
        g = rmat(80, 500, seed=1)
        p = powerlaw_partition(g.src, g.dst, g.num_nodes, 4)
        t = traffic_from_partition(p, g.src, g.dst)
        pl = random_placement(t.num_logical, topo, seed=0)
        (b,) = simulate_batch([t], [pl], backend="numpy")
        s = simulate(t, pl)
        assert b.exec_time_s == pytest.approx(s.exec_time_s, rel=1e-12)
        assert b.t_serialization_s == pytest.approx(s.t_serialization_s, rel=1e-12)

    def test_mixed_topologies_in_one_batch(self):
        """Groups with different topologies evaluate independently but return
        in input order."""
        t1, p1 = _configs(1, 4, auto_mesh_for_parts(4, "mesh2d"))
        t2, p2 = _configs(1, 4, auto_mesh_for_parts(4, "fbutterfly"), seed=5)
        traffics, placements = t1 + t2, p1 + p2
        batched = simulate_batch(traffics, placements, backend="numpy")
        for t, p, b in zip(traffics, placements, batched):
            assert b.exec_time_s == pytest.approx(simulate(t, p).exec_time_s, rel=1e-12)

    def test_batched_faster_than_serial_loop(self):
        """Acceptance: a ≥4-config sweep is measurably faster batched."""
        traffics, placements = _configs(8, 16)  # 16 configs on an 8×8 mesh
        assert len(traffics) >= 4
        simulate_batch(traffics, placements, backend="numpy")  # warm caches
        t0 = time.perf_counter()
        simulate_batch(traffics, placements, backend="numpy")
        t_batched = time.perf_counter() - t0
        t0 = time.perf_counter()
        simulate_serial(traffics, placements)
        t_serial = time.perf_counter() - t0
        assert t_batched < t_serial, (t_batched, t_serial)

    def test_batched_weighted_hops_matches_placement(self):
        topo = Mesh2D(4, 4)
        rng = np.random.default_rng(0)
        sites, weights, expect = [], [], []
        for i in range(5):
            w = rng.random((8, 8))
            pl = random_placement(8, topo, seed=i)
            sites.append(pl.site)
            weights.append(w)
            expect.append(pl.weighted_hops(w))
        got = batched_weighted_hops(np.stack(weights), np.stack(sites), topo, backend="numpy")
        np.testing.assert_allclose(got, expect, rtol=1e-12)

    def test_routing_operator_covers_fbutterfly(self):
        """FB: ≤2 links per route, one per differing dimension."""
        topo = FlattenedButterfly(3, 3)
        op = routing_operator(topo)
        per_pair = np.asarray(op.sum(axis=0)).reshape(9, 9)
        d = topo.distance_matrix()
        np.testing.assert_array_equal(per_pair, d)

    @pytest.mark.parametrize("topo", [Torus2D(4, 4), Torus2D(5, 3)])
    def test_routing_operator_matches_torus_wraparound_metric(self, topo):
        """Torus: the operator's per-pair link count equals the wraparound
        hop metric (ROADMAP: link loads previously stepped the long way)."""
        op = routing_operator(topo)
        n = topo.num_nodes
        per_pair = np.asarray(op.sum(axis=0)).reshape(n, n)
        np.testing.assert_array_equal(per_pair, topo.distance_matrix())

    def test_torus2d_batched_matches_serial(self):
        topo = Torus2D(4, 4)
        traffics, placements = _configs(2, 4, topo)
        batched = simulate_batch(traffics, placements, backend="numpy")
        for t, p, b in zip(traffics, placements, batched):
            s = simulate(t, p)
            assert b.exec_time_s == pytest.approx(s.exec_time_s, rel=1e-12)
            assert b.t_serialization_s == pytest.approx(s.t_serialization_s, rel=1e-12)


class TestSweepCache:
    def test_trace_roundtrip_identical(self, tmp_path):
        g = rmat(100, 700, seed=2)
        c1 = SweepCache(tmp_path)
        tr1 = c1.trace(g, "bfs")
        assert c1.stats.trace_misses == 1
        c2 = SweepCache(tmp_path)  # fresh instance, same dir
        tr2 = c2.trace(g, "bfs")
        assert c2.stats.trace_hits == 1 and c2.stats.trace_misses == 0
        np.testing.assert_array_equal(tr1.edge_activity, tr2.edge_activity)
        np.testing.assert_array_equal(tr1.vertex_activity, tr2.vertex_activity)
        assert tr1.num_iterations == tr2.num_iterations

    def test_traffic_identical_on_second_run(self, tmp_path):
        """Acceptance: the sweep cache returns identical traffic matrices."""
        g = rmat(100, 700, seed=3)
        p = powerlaw_partition(g.src, g.dst, g.num_nodes, 4)
        c = SweepCache(tmp_path)
        tr = c.trace(g, "pagerank", max_iterations=10)
        t1 = c.traffic(g, p, tr)
        t2 = c.traffic(g, p, tr)
        assert c.stats.traffic_hits == 1
        np.testing.assert_array_equal(t1.bytes_matrix, t2.bytes_matrix)
        assert t1.phase_bytes == t2.phase_bytes

    def test_cache_key_is_content_sensitive(self, tmp_path):
        g1 = rmat(100, 700, seed=4)
        g2 = rmat(100, 700, seed=5)
        assert graph_digest(g1) != graph_digest(g2)
        c = SweepCache(tmp_path)
        c.trace(g1, "bfs")
        c.trace(g2, "bfs")  # different content → miss, not a stale hit
        assert c.stats.trace_misses == 2

    def test_disabled_cache_recomputes(self):
        g = rmat(64, 300, seed=6)
        c = SweepCache(None)
        c.trace(g, "bfs")
        c.trace(g, "bfs")
        assert c.stats.trace_misses == 2


class TestGridAndSweep:
    def test_paper_grid_shape(self):
        grid = GRIDS["paper"]
        cfgs = grid.expand()
        assert len(cfgs) == grid.num_configs == 48
        assert sum(c.is_baseline for c in cfgs) == 24

    def test_unknown_grid_raises(self):
        with pytest.raises(ValueError, match="unknown grid"):
            grid_by_name("nope")

    def test_torus_grid_shape(self):
        """The wrap-link grid crosses mesh2d/torus2d at two mesh sizes under
        three schemes: pinned greedy (every searched config takes the batched
        construction), the constructive `auto` arm (torus-native layouts on
        torus2d, no search), and the random baseline."""
        grid = GRIDS["torus"]
        cfgs = grid.expand()
        assert len(cfgs) == grid.num_configs == 72
        assert {c.topology for c in cfgs} == {"mesh2d", "torus2d"}
        assert {c.num_parts for c in cfgs} == {16, 25}
        assert {c.placement for c in cfgs} == {"greedy", "auto", "random"}
        assert sum(c.is_baseline for c in cfgs) == 24

    def test_run_cli_compile_cache_at_fixed_checkout_path(self, monkeypatch):
        """Without JAX_COMPILATION_CACHE_DIR the sweep CLI keeps JAX's
        compile cache at artifacts/jax_cache/ of the checkout."""
        import jax

        from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            assert enable_compile_cache() == CHECKOUT_CACHE_DIR
            assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE_DIR
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        assert CHECKOUT_CACHE_DIR == os.path.join(REPO, "artifacts", "jax_cache")

    def test_compile_cache_env_dir_wins_and_nothing_at_import(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR is used as JAX reads it, with nothing
        else set; importing the entry points configures no cache."""
        body = (
            "import jax, chip_smoke, repro.experiments.run\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "assert jax.config.jax_compilation_cache_dir == {d!r}\n"
            "assert enable_compile_cache() == {d!r}\n"
            "assert jax.config.jax_compilation_cache_dir == {d!r}\n"
        )
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src") + os.pathsep + REPO)
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        subprocess.run([sys.executable, "-c", body.format(d=str(tmp_path))], env=env,
                       check=True, timeout=120)
        env.pop("JAX_COMPILATION_CACHE_DIR")
        unset = "import jax, chip_smoke, repro.experiments.run\n" \
                "assert jax.config.jax_compilation_cache_dir is None\n"
        subprocess.run([sys.executable, "-c", unset], env=env, check=True, timeout=120)

    def test_torus_sweep_smoke_through_run_cli(self, tmp_path):
        """Satellite acceptance: `run.py --grid torus --scale 0.001` stores
        the artifact whose §Torus section the paper render consumes."""
        from repro.experiments.run import main as run_main

        rc = run_main(
            [
                "--grid", "torus", "--scale", "0.001",
                "--cache-dir", str(tmp_path / "cache"),
                "--sweeps-dir", str(tmp_path / "sweeps"),
                "--no-serial-check", "--backend", "numpy", "-q",
            ]
        )
        assert rc == 0
        import json as json_lib

        payload = json_lib.load(open(tmp_path / "sweeps" / "torus.json"))
        assert len(payload["records"]) == 72
        ps = payload["placement_stats"]
        assert ps["batched_configs"] == 36 and ps["greedy_constructed"] == 24
        assert ps["torus_constructed"] == 12  # the torus2d constructive arm
        assert ps["serial_configs"] == 24  # the random-layout baselines
        # The physical claim the grid exists to demonstrate: under the
        # randomized baseline (mesh-spanning routes) the wrap links must cut
        # hops in every cell (measured ≥1.23× at this scale; the optimised
        # mapping hovers ~1× because its routes are already 1–2 hops).
        cells = {}
        for r in payload["records"]:
            key = (r["workload"], r["algorithm"], r["partitioner"],
                   r["placement"], r["num_parts"])
            cells.setdefault(key, {})[r["topology"]] = r
        baseline_gains = [
            pair["mesh2d"]["sim_avg_hops"] / pair["torus2d"]["sim_avg_hops"]
            for key, pair in cells.items()
            if key[2] == "random" and key[3] == "random"
            and "mesh2d" in pair and "torus2d" in pair
        ]
        assert len(baseline_gains) == 12
        assert min(baseline_gains) > 1.1, baseline_gains
        # The tentpole acceptance: on every torus2d cell the constructive
        # torus-native layout (powerlaw+auto) matches or beats the full
        # greedy+2-opt search (powerlaw+greedy) on byte-hops, with no search.
        greedy_h = {
            key[:2] + key[4:]: pair["torus2d"]["sim_byte_hops"]
            for key, pair in cells.items()
            if key[2] == "powerlaw" and key[3] == "greedy" and "torus2d" in pair
        }
        cons_h = {
            key[:2] + key[4:]: pair["torus2d"]
            for key, pair in cells.items()
            if key[2] == "powerlaw" and key[3] == "auto" and "torus2d" in pair
        }
        assert len(cons_h) == len(greedy_h) == 12
        for cell_key, rec in cons_h.items():
            assert rec["placement_method"] == "torus_quad"  # no search ran
            assert rec["sim_byte_hops"] <= greedy_h[cell_key] * (1 + 1e-9), cell_key
        from repro.experiments.report import _torus_section

        section = _torus_section(payload)
        assert "§Torus" in section and "wrap-link" in section.lower()
        assert "Constructive torus layouts vs greedy+2-opt" in section
        assert "search-time saving" in section

    def test_mini_sweep_end_to_end(self, tmp_path):
        grid = grid_by_name("mini")
        res = run_sweep(grid, cache_dir=str(tmp_path), measure_serial=True, backend="numpy")
        assert len(res.records) == 3
        comps = figure_comparisons(res.records)
        assert len(comps) == 2  # powerlaw+quad and powerlaw+greedy vs baseline
        for c in comps:
            # The proposed mapping must beat the randomized baseline.
            assert c["hop_decrease"] > 1.0
            assert c["speedup"] > 1.0
            assert c["energy_ratio"] > 1.0
        # Batched results equal per-config simulate() on the same inputs.
        for r in res.records:
            assert r.result.exec_time_s > 0
        # The batched placement engine ran (quad + greedy configs), the
        # greedy config through the stacked constructor, with H no worse
        # than the serial two_opt search it replaces.
        ps = res.placement_stats
        assert ps["batched_configs"] >= 2
        assert ps["greedy_constructed"] >= 1
        assert ps["h_worse_than_serial_configs"] == 0
        assert ps["h_vs_serial_max_ratio"] <= 1.0 + 1e-9
        assert any("2opt[batch]" in r.placement_method for r in res.records)

    def test_sweep_reuses_cache_on_second_run(self, tmp_path):
        grid = grid_by_name("mini")
        r1 = run_sweep(grid, cache_dir=str(tmp_path), measure_serial=False, backend="numpy")
        r2 = run_sweep(grid, cache_dir=str(tmp_path), measure_serial=False, backend="numpy")
        assert r2.cache_stats["trace_hits"] >= 1
        assert r2.cache_stats["trace_misses"] == 0
        for a, b in zip(r1.records, r2.records):
            assert a.result.exec_time_s == pytest.approx(b.result.exec_time_s, rel=1e-12)


CSV_ROW = re.compile(r"^[\w/.\-]+,\d+(\.\d+)?,\S.*$")


class TestBenchmarkContract:
    def test_run_py_emits_csv_rows_on_tiny_grid(self, tmp_path):
        """Acceptance: benchmarks/run.py → well-formed name,us_per_call,derived."""
        env = dict(
            os.environ,
            PYTHONPATH=os.path.join(REPO, "src"),
            BENCH_SCALE="0.0008",
            BENCH_PARTS="4",
            BENCH_CACHE=str(tmp_path),
        )
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
             "--only", "skew,hop_count,placement,speedup,energy"],
            capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        lines = [l for l in out.stdout.splitlines() if l.strip()]
        assert lines[0] == "name,us_per_call,derived"
        body = [l for l in lines[1:] if "," in l]
        assert len(body) >= 4 + 4 + 2 + 12 + 12  # skew+fig5+placement+fig7+fig8
        for line in body:
            assert CSV_ROW.match(line), line
        assert any(l.startswith("fig7_speedup/") for l in body)
        assert any(l.startswith("fig8_energy/") for l in body)
        assert any(l.startswith("placement/serial_loop") for l in body)
        placement_rows = [l for l in body if l.startswith("placement/batched")]
        assert placement_rows
        for row in placement_rows:  # batched search must never worsen H
            h_ratio = float(row.split("h_max_ratio=")[1].split(";")[0])
            assert h_ratio <= 1.0 + 1e-6, row

    def test_report_writer_outputs_both_files(self, tmp_path):
        from repro.experiments.report import write_outputs

        grid = grid_by_name("mini")
        res = run_sweep(grid, cache_dir=str(tmp_path / "cache"), measure_serial=False,
                        backend="numpy")
        md, js = write_outputs(
            res,
            md_path=str(tmp_path / "EXPERIMENTS.md"),
            json_path=str(tmp_path / "BENCH_sweep.json"),
            dryrun_dir=str(tmp_path / "nodir"),
            perf_dir=str(tmp_path / "nodir"),
            sweeps_dir=str(tmp_path / "nodir"),
        )
        text = open(md).read()
        for section in ("## §Calibration", "## §Dry-run", "## §Roofline", "## §Perf",
                        "## Fig. 5", "## Fig. 7"):
            assert section in text, section
        import json as json_lib

        payload = json_lib.load(open(js))
        assert payload["records"] and payload["comparisons"]
        assert payload["grid"]["name"] == "mini"
        assert payload["placement_stats"]["batched_configs"] >= 1

    def test_extra_sweep_artifacts_render_sections(self, tmp_path):
        """§Ablation / §Mesh-scaling / §Torus render from artifacts/sweeps/*.json."""
        from repro.experiments.report import save_sweep_artifact, write_outputs

        grid = grid_by_name("mini")
        res = run_sweep(grid, cache_dir=str(tmp_path / "cache"), measure_serial=False,
                        backend="numpy")
        sweeps = tmp_path / "sweeps"
        # Stand-ins for the secondary grids: payload shape is what the
        # renderers consume, the grid name keys the section.
        for name in ("ablation", "meshscale", "torus"):
            import dataclasses as dc

            res2 = dc.replace(res, grid=dc.replace(res.grid, name=name))
            save_sweep_artifact(res2, str(sweeps))
        md, _ = write_outputs(
            res,
            md_path=str(tmp_path / "E.md"),
            json_path=str(tmp_path / "B.json"),
            dryrun_dir=str(tmp_path / "nodir"),
            perf_dir=str(tmp_path / "nodir"),
            sweeps_dir=str(sweeps),
        )
        text = open(md).read()
        assert "## §Ablation" in text
        assert "## §Mesh scaling" in text
        assert "## §Torus" in text


class TestFreshnessAudit:
    def _written(self, tmp_path):
        from repro.experiments.report import write_outputs

        res = run_sweep(
            grid_by_name("mini"), cache_dir=str(tmp_path / "cache"),
            measure_serial=False, backend="numpy",
        )
        md, js = write_outputs(
            res,
            md_path=str(tmp_path / "E.md"),
            json_path=str(tmp_path / "B.json"),
            dryrun_dir=str(tmp_path / "nodir"),
            perf_dir=str(tmp_path / "nodir"),
            sweeps_dir=str(tmp_path / "sweeps"),
        )
        return res, md, js

    def test_fresh_report_passes(self, tmp_path):
        from repro.experiments.report import experiments_md_issues

        _, md, js = self._written(tmp_path)
        assert experiments_md_issues(md, js, str(tmp_path / "sweeps")) == []

    def test_unrendered_sweep_artifact_is_stale(self, tmp_path):
        import dataclasses as dc

        from repro.experiments.report import experiments_md_issues, save_sweep_artifact

        res, md, js = self._written(tmp_path)
        res2 = dc.replace(res, grid=dc.replace(res.grid, name="torus"))
        save_sweep_artifact(res2, str(tmp_path / "sweeps"))  # stored after the render
        issues = experiments_md_issues(md, js, str(tmp_path / "sweeps"))
        assert issues and "torus" in issues[0]

    def test_rendered_section_with_missing_artifact_is_stale(self, tmp_path):
        import dataclasses as dc

        from repro.experiments.report import (
            experiments_md_issues,
            save_sweep_artifact,
            write_outputs,
        )

        res = run_sweep(
            grid_by_name("mini"), cache_dir=str(tmp_path / "cache"),
            measure_serial=False, backend="numpy",
        )
        sweeps = tmp_path / "sweeps"
        res2 = dc.replace(res, grid=dc.replace(res.grid, name="torus"))
        save_sweep_artifact(res2, str(sweeps))
        md, js = write_outputs(
            res,
            md_path=str(tmp_path / "E.md"), json_path=str(tmp_path / "B.json"),
            dryrun_dir=str(tmp_path / "nodir"), perf_dir=str(tmp_path / "nodir"),
            sweeps_dir=str(sweeps),
        )
        assert experiments_md_issues(md, js, str(sweeps)) == []
        os.remove(sweeps / "torus.json")  # report still renders §Torus
        issues = experiments_md_issues(md, js, str(sweeps))
        assert issues and "torus" in issues[0] and "missing" in issues[0]

    def test_mismatched_payload_is_stale(self, tmp_path):
        import json as json_lib

        from repro.experiments.report import experiments_md_issues

        _, md, js = self._written(tmp_path)
        payload = json_lib.load(open(js))
        payload["records"] = payload["records"][:-1]  # drift the config count
        json_lib.dump(payload, open(js, "w"))
        issues = experiments_md_issues(md, js, str(tmp_path / "sweeps"))
        assert issues and "config count" in issues[0]

    def test_check_cli_exit_codes(self, tmp_path):
        from repro.experiments.report import main as report_main

        _, md, js = self._written(tmp_path)
        args = ["--check", "--md", md, "--json", js, "--sweeps-dir", str(tmp_path / "sweeps")]
        assert report_main(args) == 0
        os.remove(js)
        assert report_main(args) == 1
