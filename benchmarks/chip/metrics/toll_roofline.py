"""Share of the HBM roofline Linear Road's toll step reaches.

The least time of a step is its necessary bytes over peak HBM bandwidth.
Per vehicle the rules read, as 4-byte words, the speed, xway, dir and
segment of each report in LAV's window (10 slots) and the lane and
position of the last 4 reports (stopped vehicles), and read and write
once the vehicle's state (balance, tick of the last charge, pending toll,
the 6 fields of its notice). No correct step reads less: the statistics
need every report of the window, and the stopped-vehicle rule every
position of the last 4. The count depends only on the configuration (its
vehicles), never on how the step is built.

The reader's context carries no fleet size, so it takes the vehicles from
the configuration of the cells that list this metric (as the run loaded
them), and reads nothing where they disagree.
"""
import cells
import readings

PROGRAM = True
LAV_SLOTS, STOP_SLOTS, STATE_WORDS = 10, 4, 9


def least_bytes(vehicles: int) -> float:
    return 4.0 * vehicles * (LAV_SLOTS * 4 + STOP_SLOTS * 2 + 2 * STATE_WORDS)


def vehicles():
    here = [w for m in cells.benchmark()["per_layer"] if m["name"] == "toll_roofline"
            for w in m.get("workloads", [])]
    sizes = {int(cells.load_cell(w).config["sim"]["n_clients"]) for w in here}
    return sizes.pop() if len(sizes) == 1 else None


def read(ctx):
    ms, n = readings.read("toll_step_ms", ctx), vehicles()
    if ms is None or n is None:
        return None
    return 100.0 * least_bytes(n) / ctx.peaks["hbm_bytes_per_s"] / (1e-3 * ms)
