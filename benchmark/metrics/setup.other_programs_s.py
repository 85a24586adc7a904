"""Seconds JAX spent tracing, lowering and compiling (or reading back) every
program of the run that is not the step program: the init programs, the
reference's loss, the checks, every eager operation's own little program.
Logs the table: each program by name, when it was requested, how it was
obtained, its three durations, dearest first - which also names a program
requested inside the window when ``correct``'s clause (c) fails. Later
requests for the step program (``train_scoped``'s rebuild) are left out and
logged on their own. Nothing to read from a program without the recorder."""

LAYER = "compile cache"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    from benchmark.harness import startup
    filed = startup.requests()
    if filed is None:
        return None
    startup.log_programs(run["log"])
    return float(sum(map(startup.seconds_of, startup.split(filed)[2])))
