"""A static cell over four chips runs through the harness as committed
cells do: the points sharded over a four-device mesh, an open loop with
no writer, the selection rounds across the shards, the float64 check.

The cell is built here, at a size a CPU test run can hold, on four of
the host's placeholder devices; the harness's look for a chip is
skipped, and everything else of a run is driven."""

import jax

from bench import run, spec

SEED = 2**31 + 4747


def tiny_x4() -> spec.Cell:
    config = {
        "chips": 4, "backing": "static", "dim": 16, "n_points": 4096,
        "clusters": 16, "center_scale": 8.0,
        "service": {"l_max": 32, "sampler": "selection", "route": "exact",
                    "search": "exact"},
        "check": {"sample": 64, "limits": {"missing": 0, "stale": 0,
                                           "id_miss": 0, "dist_err": 0.15}},
    }
    traffic = {"loop": "open", "rate_qps": 100,
               "l_mix": [[10, 0.5], [32, 0.5]]}
    return spec.Cell(
        name="tiny-x4.poisson", chips=4, config=config, traffic=traffic,
        end_to_end=[{"name": "setup_s", "unit": "s"},
                    {"name": "p50_ms", "unit": "ms"}],
        per_layer=[])


def test_a_static_four_chip_cell_runs_correct():
    cell = tiny_x4()
    res = run.run_cell(cell, SEED, 1.0, False, jax.devices("cpu")[:4])
    assert res["correct"] is True, res["check"]
    assert res["check"]["stale"]["value"] == 0
    assert res["check"]["missing"]["value"] == 0
    assert res["metrics"]["p50_ms"]["value"] > 0
    assert res["device"]["count"] == 4
    assert res["attempted"] == 100
