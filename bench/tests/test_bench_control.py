"""The control of each cell, at a size a test run holds: the reference
computed in the precision below the configuration's, put in the
program's place, reads further from the reference than the program does
and fails a limit that the program keeps.  On the chip the same readings,
at the cells' own sizes, set the limits (`python3 -m bench.control`)."""
from bench import spec
from bench.tests import tiny


def _readings(cell, seeds, control):
    out = []
    driver = spec.load_module("drivers", cell.config["driver"])
    driver.control(cell, seeds, control,
                   lambda side, seed, r: out.append((side, seed, r)))
    return out


def _fails(cell, r):
    return [k for k, v in r.items() if v > cell.config["limits"][k]]


def test_fleet_control_fails_where_the_program_passes():
    cell = tiny.fleet_cell()
    got = _readings(cell, [11, 12], {11})
    prog = [r for side, _, r in got if side == "program"]
    (ctrl,) = [r for side, _, r in got if side == "control"]
    assert len(prog) == 2 and not any(_fails(cell, r) for r in prog)
    assert {"hist_cells_differ", "tpa_mean_gap"} <= set(_fails(cell, ctrl))
    for k in ("sums_rel_err", "tpa_mean_gap"):
        assert ctrl[k] > 10 * max(r[k] for r in prog)


def test_train_control_reads_further_than_the_program():
    cell = tiny.train_cell()
    got = _readings(cell, [21], {21})
    by = {side: r for side, _, r in got}
    assert not _fails(cell, by["program"])
    for k in ("loss_rel_gap", "grad1_norm_gap"):
        assert by["control"][k] > 3 * by["program"][k]
    assert _fails(cell, by["half_batch"])
