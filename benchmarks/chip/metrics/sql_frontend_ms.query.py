"""SQL front door (``sql/``): parse and compile (with type inference,
which runs inside compile) per query, from the flight recorder's
``parse`` and ``compile`` spans. Moves ``query_s``."""
import layers


def read(ctx):
    return layers.span_ms(ctx, "parse", "compile")
