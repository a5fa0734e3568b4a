"""Share of the tick loop's leaf-op time in the ``tick.accounting`` scope:
byte accounting, comm-phase completion and the iteration record."""
import scopes


def read(ctx):
    return scopes.share(ctx, "tick.accounting")
