"""``sdar_noise_loss_time_share.train``: device self time of what a
block-diffusion step has outside its decoder's layers (the noising op
and the roll of its weights, the roll of the noised rows, the last norm,
the head over the vocabulary slice and its softmax, and the weighted
cross-entropy under ``ff.loss``), forward, backward and recomputation,
over device busy time in the traced groups, in percent."""
from benchmarks.harness import bd_reduce


def read(ctx):
    return bd_reduce.noise_and_loss_share(ctx)
