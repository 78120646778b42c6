"""``test_flex_bwd_fused.py``'s cases at head_dim 256 (the latent form's
head width: two vregs of lanes a row, the dq tile twice as wide), a file of
their own so that no file is a worker's whole run (docs/testing.md). Same
function, same ids."""

from .test_flex_bwd_fused import fused_backward_test

test_fused_backward_matches_the_reference = fused_backward_test(256)
