"""step_mfu: model FLOPs of the steps completed in the traced window
(PaLM's count, bench/counts.py) over window x chips x peak bf16 FLOP/s."""


def read(ctx):
    win = ctx["window"]
    if win.steps == 0:
        return None
    conf = ctx["cell"].conf
    flops = win.tokens * ctx["counts"].flops_per_token(conf, ctx["seq_len"])
    return 100.0 * flops / (win.seconds * ctx["chips"]
                            * ctx["peaks"]["bf16_flops"])
