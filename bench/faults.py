"""Faults planted in the timed path, each where the program produces what it
breaks, that the check has to catch.  `plant(patch)` installs one through
`patch(owner, attribute, value)`: pytest's `monkeypatch.setattr` in the
tests, `setattr` in `control.py --plant <fault>` on the chip.

Each fault stands for one the contract names: a step that returns its state
unchanged (`unchanged_step`, `skip_descent`), half of the batch left out
(`half_grid`), and an answer altered where it is produced (the rest).
"""
from __future__ import annotations


def unchanged_step(patch):
    """The vertex program's step returns its state unchanged."""
    import repro.graph.vertex_program as vp

    def step(program, graph, props, active, aux):
        return props, active, active[graph[0]] & graph[2]

    patch(vp, "traced_step", step)


def skip_descent(patch):
    """The placement search hands back its start unsearched: the descent
    runs no step."""
    import repro.experiments.placement_batch as pb

    real = pb.batch_descend

    def unsearched(*args, **kw):
        return real(*args, **dict(kw, max_steps=0))

    patch(pb, "batch_descend", unsearched)


def half_grid(patch):
    """The sweep evaluates every other config of its grid."""
    from repro.experiments.grid import GridSpec

    real = GridSpec.expand
    patch(GridSpec, "expand", lambda self: real(self)[::2])


def simulate_altered(patch):
    """One config's simulated time altered where `simulate_batch` makes it."""
    import dataclasses

    import repro.experiments.sweep as sweep

    real = sweep.simulate_batch

    def altered(*args, **kw):
        out = real(*args, **kw)
        out[0] = dataclasses.replace(out[0], exec_time_s=out[0].exec_time_s * 1.001)
        return out

    patch(sweep, "simulate_batch", altered)


def nocsim_altered(patch):
    """The jax open-loop window recursion reports every link serving 0.1 % more."""
    import repro.nocsim.batch as nb

    real = nb._step_jax

    def altered(inj, backlog0=None):
        serviced, backlog = real(inj, backlog0)
        return serviced * 1.001, backlog

    patch(nb, "_step_jax", altered)


def credit_altered(patch):
    """The jax credit recursion reports every link serving 0.1 % more."""
    import repro.nocsim.credit as cr

    real = cr._credit_step_jax

    def altered(program):
        step = real(program)

        def wrapped(xs, carry):
            (serviced, *rest), out = step(xs, carry)
            return (serviced * 1.001, *rest), out

        return wrapped

    patch(cr, "_credit_step_jax", altered)


FAULTS = {f.__name__: f for f in (unchanged_step, skip_descent, half_grid, simulate_altered,
                                  nocsim_altered, credit_altered)}
