"""Model operations of the tokens the window served (prompts with
attention over their own prefix, decoded tokens at their context, the TT
FFNs at their factorised count, the LM head per emitted token), over the
window, over the chip's bf16 peak."""
SOURCE = "host_clock"
UNIT = "%"
LAYER = "model step (models/model.py mixed_step, decode_step)"
MOVES = "itl_p50_ms"


def read(w):
    fl = w.served_flops()
    if not fl:
        return None
    return 100.0 * fl / w.window_s / w.peaks["bf16_flops_per_s"]
