"""Device time per paged decode program, in ms: the program in whose
events the decode-attention kernel runs. Moves ``itl_p95_ms``."""
from chipbench import kernels, tracing


def read(obs):
    prog = kernels.program_with(obs.trace, "decode_attention")
    if prog is None:
        return None
    return 1e3 * tracing.seconds(prog) / len(prog)
