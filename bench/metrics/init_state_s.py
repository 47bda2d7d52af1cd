"""Host seconds of ``engine.init``: zones, rigid painting, the initial state."""


def read(ctx):
    return ctx["init_state_s"]
