"""XLA compiles per job in the window: JAX's backend-compile events
(program counter)."""


def read(record, trace):
    return record["compiles"] / len(record["units"])
