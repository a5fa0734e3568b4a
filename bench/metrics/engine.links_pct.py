"""Share of the tick loop's leaf-op time in the ``tick.links`` scope:
RED enqueue, serve, and the routing of departures."""
import scopes


def read(ctx):
    return scopes.share(ctx, "tick.links")
