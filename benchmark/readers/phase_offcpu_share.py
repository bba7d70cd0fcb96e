"""Of the seconds the engine-loop thread spent in the given phases, the share
it was not on a CPU, in per cent, over the window: 100 x (change of
``phase_cpu_wall_s`` less change of ``phase_cpu_s``) over change of
``phase_cpu_wall_s``, summed over ``phases``. The program reads the thread's
CPU clock at the boundaries of one step in a few; ``phase_cpu_s`` holds the CPU
seconds of those phases and ``phase_cpu_wall_s`` their wall seconds, closed
phases only. For phases that wait for no device and no queue the difference
is time the thread wanted to run and did not: the interpreter lock, the
scheduler. Nothing where the program keeps no CPU seconds by phase.
Parameters: ``phases``."""


def read(ctx, params):
    a, b = ctx["before"]["engine"], ctx["after"]["engine"]
    if not all(k in e for e in (a, b)
               for k in ("phase_cpu_s", "phase_cpu_wall_s")):
        return None
    wall = cpu = 0.0
    for phase in params["phases"]:
        wall += (b["phase_cpu_wall_s"].get(phase, 0.0)
                 - a["phase_cpu_wall_s"].get(phase, 0.0))
        cpu += (b["phase_cpu_s"].get(phase, 0.0)
                - a["phase_cpu_s"].get(phase, 0.0))
    if wall <= 0:
        return None
    return 100.0 * (wall - cpu) / wall
