"""Bytes the RPC clients received per answered query."""


def read(run):
    done = run.answered("k_hop")
    return run.received_bytes / len(done) if done else None
