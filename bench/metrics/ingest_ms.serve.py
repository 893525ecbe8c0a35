"""ingest_ms.serve: mean host time per accepted uplink in the server's
``codec.decode`` span (decode, validation and the ring write nested in it),
over the traced rounds."""


def read(ctx):
    red = ctx.get("reduced")
    durs = [] if red is None else red.spans.get("codec.decode", [])
    return 1e3 * sum(durs) / len(durs) if durs else None
