"""A whole run of each cell at a small size on the CPU: the result line,
the refusal off the chip, and the control that ``correct`` must catch.

The runs skip the harness's look for a chip and drive everything else:
data from the seed, the store load or the static server, warm-up, the
window's traffic, and the comparison with the float64 reference.
"""

import json

import pytest

from bench import control, run, spec
from bench.tests.tiny import SECONDS, SEED, devices, run_tiny, tiny

CELLS = ["msturing100-store.stream", "msturing100-store.batch"]


@pytest.mark.parametrize("name", CELLS)
def test_result_line(name):
    res = run_tiny(name)
    line = json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "check"
    assert line["correct"] is True, line["check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    full = spec.load_cell(name)
    assert set(line["metrics"]) == {m["name"] for m in full.end_to_end}
    for m in full.end_to_end:
        assert line["metrics"][m["name"]]["unit"] == m["unit"]
        assert line["metrics"][m["name"]]["value"] > 0
    assert line["device"]["count"] == full.chips
    assert "busy_s" not in line["device"]
    for v in line["check"].values():
        assert v["value"] <= v["limit"]


def test_off_the_chip_the_run_exits_without_a_result(capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "msturing100-store.stream", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_control_is_not_correct():
    """The reference at ``high`` in the program's place fails the limit
    that the program's own answers meet."""
    c = tiny("msturing100-store.stream")
    system = run.System(c, SEED, devices(c))
    load = run.Load(c, SEED, SECONDS, system)
    load.warm_up(SEED, system.centers)
    win = load.run(SECONDS)
    got = control.readings(system, load, win, SEED, 64)
    load.release()
    system.close()
    limits = c.config["check"]["limits"]
    assert got["program"]["dist_err"] <= limits["dist_err"]
    assert got["program"]["id_miss"] == 0
    assert got["control"]["dist_err"] > limits["dist_err"]
