"""compiles_in_window: programs the executor's ProgramCache built during
the window (its misses). It should read 0: every shape is warm."""

LAYER = "executor"
MOVES = "gups"


def read(run):
    return run.compiles_in_window
