"""Mean host-clock wait for ``next(loader)`` per training step in the
window: time the step loop spent waiting on the input pipeline
(``ShardedLoader`` over ``StackedSource``) rather than dispatching."""


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    return 1e3 * ctx["loader_wait_s"]
