"""Share of its roofline the paged-attention kernel reaches in a
configuration with window layers: paged_attention_roofline's reading
(the least time for the work the live contexts need, counted by
counts.attn_kernel_work, which counts a `sliding_attention` layer over
at most the window's newest positions, over the kernel's device time),
for the cells whose list that metric does not name. Moves
tpot_p90_ms."""

import spec

read = spec.load_reader("paged_attention_roofline")
