"""Duration of the program's ``setup/model_init`` spans before the
window: the model, its parameters initialised op by op, optimizer,
algorithm, chunk resolution, feasibility checks, per-client state. From
the program's own span recorder."""

from harness import hostspans


def read(ctx):
    return hostspans.seconds_before(
        hostspans.recorder(), "setup/model_init", ctx["opened_at"]
    )
