"""The selective scan's forward kernel against its roofline: the least
time its launches of one round could take (the larger of FLOPs over the
bf16 peak and bytes over the HBM peak, from shapes: ``flops/jamba.py``
``scan_forward``, as many launches as the trace counts, each over the
tokens of one forward pass) over the time they took
(``ssm_scan_ms_per_round``, the same launches).

``peaks.json`` has no peak of the VPU or of the EUP, where this kernel's
work runs (elementwise: one ``exp``, two multiply-adds and a product a
channel, state and position), so its FLOPs stand against the MXU's bf16
peak and the share reads low by construction: it moves with the kernel's
time, it is not a distance to what the chip could do."""

import importlib.util
import os

from harness import scopes


def _launches(ctx):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ssm_scan_ms_per_round.py")
    spec = importlib.util.spec_from_file_location("bench_ssm_scan_ms", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.launches(ctx)


def read(ctx):
    found = _launches(ctx)
    if found is None:
        return None
    seconds, launches = found
    tokens, passes = scopes.forward_passes(ctx)
    work = scopes.load_flops(ctx).scan_forward(
        ctx["spec"]["config"]["model"], launches * tokens / passes, launches)
    return scopes.roofline_pct(ctx, 1e3 * seconds, work)
