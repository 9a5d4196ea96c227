"""Set-up seconds: from the start of the process to the window's opening
(imports, engine build, weights, programs compiled or loaded, the warm
stream).  Host clock."""


def read(ctx):
    return ctx.setup_s
