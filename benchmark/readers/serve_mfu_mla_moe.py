"""step_mfu.serve_mla_moe: ``serve_mfu``'s reading — model FLOPs of every
token prefilled or decoded between the two counter readings of the traced
window, over that time times chips times the bfloat16 peak — with the FLOPs
of a latent-attention decoder with fine-grained experts, counted in the
published (expanded) form (``bench_flops_mla_moe``)."""

import os

import bench_flops_mla_moe
import run

_serve_mfu = run.load_file(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "serve_mfu.py"))


def read(ctx):
    return _serve_mfu.read({**ctx, "flops": bench_flops_mla_moe})
