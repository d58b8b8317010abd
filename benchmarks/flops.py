"""The benchmark's own count of a model's operations: walk the jaxpr of the
plain float32 reference forward at the cell's shapes and add 2 × multiply-
accumulates of every ``conv_general_dilated`` and ``dot_general``. Nothing
else is counted (XLA's HLO cost analysis also counts elementwise work, so
this reads a little lower), and nothing of the program under test is
consulted: a change to the program cannot move the yardstick."""

import math

import jax
import jax.numpy as jnp


def _conv_flops(eqn):
    lhs, rhs = (v.aval for v in eqn.invars)
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    # rhs_spec = (out feature dim, in feature dim, *spatial dims)
    in_features = rhs.shape[dn.rhs_spec[1]]      # already per group
    window = math.prod(rhs.shape[d] for d in dn.rhs_spec[2:])
    return 2 * math.prod(out.shape) * in_features * window


def _dot_flops(eqn):
    lhs = eqn.invars[0].aval
    out = eqn.outvars[0].aval
    (lhs_contract, _), _ = eqn.params["dimension_numbers"]
    return 2 * math.prod(out.shape) * math.prod(lhs.shape[d]
                                                for d in lhs_contract)


def jaxpr_matmul_flops(jaxpr):
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "dot_general":
            total += _dot_flops(eqn)
        for value in eqn.params.values():       # pjit, custom_jvp, remat…
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += jaxpr_matmul_flops(inner)
    return total


def forward_flops_per_image(reference, include_top, batch=1):
    """2 × MACs of one image through ``reference.forward`` (a module of
    ``references/``), preprocessing included (it has no matrix product)."""
    from references import plain

    h, w = reference.INPUT_SIZE
    x = jax.ShapeDtypeStruct((batch, h, w, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda: init_variables(reference, jax.random.PRNGKey(0), include_top))

    def fwd(vs, x):
        return reference.forward(plain.Scope.apply(vs), x,
                                 include_top=include_top)

    closed = jax.make_jaxpr(fwd)(variables, x)
    return jaxpr_matmul_flops(closed.jaxpr) / batch


def init_variables(reference, key, include_top):
    """Every leaf ``reference.forward`` asks for, drawn from ``key`` — one
    traceable function, so one jitted call makes all the weights on the
    device (the forward pass traced beside them is dead code)."""
    from references import plain

    scope = plain.Scope.init(key)
    h, w = reference.INPUT_SIZE
    reference.forward(scope, jnp.zeros((1, h, w, 3), jnp.float32),
                      include_top=include_top)
    return scope.made
