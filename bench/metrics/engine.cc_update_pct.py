"""Share of the tick loop's leaf-op time in the ``tick.cc_update`` scope:
the CC-tick kernel with the packing and unpacking of its operands."""
import scopes


def read(ctx):
    return scopes.share(ctx, "tick.cc_update")
