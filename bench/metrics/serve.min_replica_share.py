"""The least-used replica's share of the window's microbatches, in %.

``Engine.stats()`` microbatches of each replica, counted over the window.
50% is an even split over two replicas; near 0 one replica has stopped
being routed to and capacity has halved while that lasts.
"""


def read(run):
    per = run.engine["replica_microbatches"]
    total = sum(per)
    return min(per) / total * 100.0 if total and len(per) > 1 else None
