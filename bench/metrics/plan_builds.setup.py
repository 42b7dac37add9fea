"""Plans the program built during set-up: its plan cache's misses from
the process's start to the end of warm-up (a program counter)."""


def read(run):
    return run.plan_builds_setup
