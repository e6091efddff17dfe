"""The whole training step's share of the chips' bf16 peak, in percent:
model operations per unique token times unique tokens per second, over
chips times one chip's peak.  Replicated and padding rows are cost, not
output, so they count for nothing here."""


def read(run):
    fl = run.flops
    per_token = fl.train_flops_per_token(run.config,
                                         run.traffic["trainer"]["seq_len"])
    return 100.0 * per_token * run.tokens_per_s / (
        run.chips * fl.peak(run.device_kind))
