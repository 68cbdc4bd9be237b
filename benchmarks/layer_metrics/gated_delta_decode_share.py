"""linear attention layer (nn/layers/gated_deltanet.py): the share of the
decode program's device time that its `gated_delta_decode` kernel events
take, over the traced window: the summed device time of those events over
the summed device time of the decode programs. What is left is the step's
matrix products (weights read once), the convolution, the norms and gates
around the kernel, the full layers' attention and the head. A program
without the kernel gives nothing to read."""
from harness import serve_facts, xplane

KERNEL = r"gated_delta_decode"


def read(facts):
    steps = serve_facts.decode_steps_traced(facts)
    if not steps:
        return None
    spent, calls = xplane.kernel_seconds(facts["traced"]["chips"], KERNEL)
    if not calls:
        return None
    return 100.0 * spent / sum(steps)
