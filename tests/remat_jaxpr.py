"""What tests/remat_cases.py and tests/test_minicpm_sala_model.py
read from a gradient's jaxpr: which forward kernels and which forward matmuls
it holds, a replay's among them."""
import collections

import jax


def equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


def kernel_calls(jaxpr):
    """Every ``pallas_call`` under ``jaxpr``, counted by the kernel's name."""
    return collections.Counter(
        eqn.params["jaxpr"].debug_info.func_name for eqn in equations(jaxpr)
        if eqn.primitive.name == "pallas_call")


def forward_matmuls(jaxpr):
    """Every ``dot_general`` under ``jaxpr`` that a forward pass or a replay
    runs (and no backward), counted by the two flax names its name stack ends
    in."""
    def forward(stack):
        return ("jvp(" in stack and "transpose" not in stack
                or "rematted_computation" in stack)

    stacks = (str(eqn.source_info.name_stack) for eqn in equations(jaxpr)
              if eqn.primitive.name == "dot_general")
    return collections.Counter(
        "/".join(stack.split("/")[-2:]) for stack in stacks if forward(stack))
