"""What a traced call weighs: set-up pays for every equation of it (tracing,
lowering, a kernel's compile)."""

from __future__ import annotations


def equations(jaxpr) -> int:
    """The equations of a jaxpr, those of every jaxpr among their parameters
    (a jit's, a kernel's body, a loop's, a `pl.when`'s branches) counted in."""
    def inner(value):
        if hasattr(value, "eqns"):
            yield value
        elif hasattr(value, "jaxpr"):
            yield from inner(value.jaxpr)
        elif isinstance(value, (tuple, list)):
            for v in value:
                yield from inner(v)

    return sum(1 + sum(equations(j) for v in eqn.params.values()
                       for j in inner(v)) for eqn in jaxpr.eqns)
