"""Model FLOP utilization of the traced rounds: model FLOPs (forward and
backward, recomputation not counted) of every token of every round, over
the rounds' host-clock time times the chips times the chip's bf16 peak."""
from bench import counting, tokens


def read(ctx):
    cell = ctx["cell"]
    flops = (ctx["rounds"] * tokens.round_tokens(cell.traffic)
             * counting.train_flops_per_token(cell.conf, cell.traffic["seq_len"]))
    return 100.0 * flops / (ctx["host_window_s"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops"])
