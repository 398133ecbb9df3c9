"""Valid operations confirmed inside the window over the window's whole
length (stats.whole_window_rate: a stall counts as the time it took).
The harness's own clock; nothing is read from the program."""
import stats


def read(spec, run):
    return stats.whole_window_rate(
        [op.done for op in run["released"] if op.valid],
        run["t0"], run["t1"])
