"""Device time per execution of the jitted megastep program
(``core/megastep.py`` ``_megastep``) in its planning stages, in ms: the
ops under the ``assign``, ``bounds`` and ``schedule`` named scopes
(stages 1-3, ``_assign_bounds_schedule``), found by the ``tf_op`` stat of
each op's event metadata in the run's trace. Copies XLA inserts carry
no scope and are not counted; a program without the scopes reads
nothing."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    xplane = program_trace.run_xplane(run)
    if xplane is None:
        return None
    return program_trace.plan_ms(run.trace, program_trace.op_scopes(xplane))
