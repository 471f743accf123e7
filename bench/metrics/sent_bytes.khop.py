"""Bytes the RPC front sent per answered k-hop query (``sent_bytes``),
the server's side of ``answer_bytes.khop``."""
from bench import counters


def read(run):
    sent = counters.delta(run, "sent_bytes")
    done = run.answered("k_hop")
    return sent / len(done) if sent is not None and done else None
