"""Requests per engine microbatch (solve), summed over replicas:
``Engine.stats()`` requests over microbatches, counted over the window."""


def read(run):
    mb = run.engine["microbatches"]
    return run.engine["requests"] / mb if mb else None
