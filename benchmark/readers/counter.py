"""A count or a time the harness itself took: ``args.name`` names it.
``warmup_s`` (the server's ``WarmUp.seconds``; under ``tpu_mesh``, SETTINGS
to first complete frame), ``compile_cache_misses`` (JAX's cache events)."""


def read(run, args):
    return run.counters.get(args["name"])
