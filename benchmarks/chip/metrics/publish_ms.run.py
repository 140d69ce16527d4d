"""Publication (``core/transactions.py``, ``core/store.py``): the
``publication_attempt`` spans per run. Moves ``run_s``."""
import layers


def read(ctx):
    return layers.span_ms(ctx, "publication_attempt")
