"""step_mfu.serve_afmoe: ``serve_mfu``'s reading — model FLOPs of every
token prefilled or decoded between the two counter readings of the traced
window, over that time times chips times the bfloat16 peak — with the FLOPs
of a decoder of window and full attention layers with fine-grained experts
(``bench_flops_afmoe``): a window layer's attention over its band alone,
the K routed experts a token chose."""

import os

import bench_flops_afmoe
import run

_serve_mfu = run.load_file(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "serve_mfu.py"))


def read(ctx):
    return _serve_mfu.read({**ctx, "flops": bench_flops_afmoe})
