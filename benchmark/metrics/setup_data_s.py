"""Duration of the program's ``setup/data`` spans before the window:
dataset and client shards, eval padding, placement of client and eval
arrays on the device, store and streamer construction. From the
program's own span recorder."""

from harness import hostspans


def read(ctx):
    return hostspans.seconds_before(
        hostspans.recorder(), "setup/data", ctx["opened_at"]
    )
