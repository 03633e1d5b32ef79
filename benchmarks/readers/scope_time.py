"""Device time a step under a set of ``jax.named_scope``s: the summed
duration of the ``XLA Ops`` events whose scope path matches, over the count
of the step module's ``XLA Modules`` events, in milliseconds a step. Counted
are the module events wholly inside the traced window on the first chip and
the operations wholly inside one of those: a step that the window's edge
cuts gives nothing to either side of the ratio.

how: {"module": substring of the module name,
      "any": [substrings of the scope path, one must be there],
      "none": [substrings that must not be there]}   (optional)

The path is the operation's ``op_name`` (``readers/_xplane.py`` says where it
is found); the backward of a scope ``s`` reads ``transpose(jvp(s))``. A trace
of a program without the scopes matches nothing and gives None.
"""

from readers import _xplane


def reduce(doc, how):
    mods, ops = _xplane.step_ops(doc, how["module"])
    total = [op[2] for op in ops if _xplane.matches(op[3], how)]
    if not mods or not total:
        return None
    return sum(total) / len(mods) / 1e6


def read(ctx, how):
    return reduce(_xplane.of_run(ctx), how)
