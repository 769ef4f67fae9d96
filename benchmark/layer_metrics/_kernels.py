"""Shared by the kernel readers: which trace ops are which Pallas kernel.

The kernels carry no ``name=`` of their own yet (PERF.md section 7): every
Pallas custom call is printed as ``branch_0_fun.<n>`` (the
``lax.platform_dependent`` branch that holds it). What tells them apart
today is the program they run in: the flash forward kernel sits in the
prefill programs, the paged decode kernel in the decode programs.
"""

CUSTOM_CALL = "/branch_0_fun"
PROGRAM = {"paged": "jit__decode", "flash": "jit__prefill"}


def kernel_seconds(obs, kernel):
    tr = obs.get("trace")
    if not tr:
        return None
    hit = [s for name, s in tr["op_seconds"].items()
           if name.startswith(PROGRAM[kernel] + CUSTOM_CALL)]
    return sum(hit) if hit else None


def time_share(obs, kernel):
    s = kernel_seconds(obs, kernel)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]
