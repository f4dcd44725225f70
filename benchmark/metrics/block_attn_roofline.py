"""The block decoder's paged attention's share of its roofline: least
time to read the live K and V pages (4 K/V heads, whole 16-token pages,
never the pool) of every slot-forward of the traced window once a layer
and to do the dot products of its 32 query heads x 4 rows
(``work_sdar.block_attn_work``), over the device time in the named
scope ``attn.pages``, which holds the step's paged-attention call and
nothing else.  Memory-bound: DMAs cap it near 85%."""
from benchmark import work_ling


def read(ctx):
    return work_ling.scope_roofline(ctx, "attn.pages", "block_attn")
