"""Mean running streams per decode step over ``max_batch``, from the
program's ``serving.decode_batch`` histogram across the window."""


def read(obs):
    if obs["kind"] != "serve":
        return None
    (c0, s0), (c1, s1) = obs["before"]["decode_batch"], \
        obs["after"]["decode_batch"]
    if c1 <= c0:
        return None
    return 100.0 * (s1 - s0) / (c1 - c0) / obs["config"]["engine"]["max_batch"]
