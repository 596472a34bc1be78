"""Duration of the program's ``setup/resume`` span before the window:
checkpoint search, load, CRC, restoring the trees. From the program's
own span recorder."""

from harness import hostspans


def read(ctx):
    return hostspans.seconds_before(
        hostspans.recorder(), "setup/resume", ctx["opened_at"]
    )
