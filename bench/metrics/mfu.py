"""The plain forward pass's operations per image (2 per multiply-
accumulate, from the net's shapes) times the traced window's images per
second, over the chips' int8 peak.  The secure program does many times this
work; this is the share of the chip that serves plain-model work."""


def read(run):
    if not run.images or run.window_s <= 0:
        return None
    ops = run.work.plaintext_ops_per_image(run.config["layers"],
                                           tuple(run.config["input_shape"]))
    chips = int(run.cell["chips"])
    return 100.0 * ops * run.images / run.window_s \
        / (chips * run.peak["int8_ops_per_s"])
