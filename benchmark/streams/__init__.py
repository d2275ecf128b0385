"""One file per kind of stream.  A traffic mix (``traffic/<name>.json``)
lists streams by ``kind``; the harness imports ``streams/<kind>.py`` and
builds its ``Stream(ctx, params, seed, seconds)``.

A stream has ``warm()`` (set-up: drive every shape the window will use),
``run()`` (the window: one thread per stream, which may start its own
clients) and ``finish()`` (after the drain).  It puts its client-clock
samples into ``ctx.samples[<name>]``, what it found wrong into
``ctx.numbers`` and what it tried into ``ctx.attempted`` / ``ctx.failed``.
"""
