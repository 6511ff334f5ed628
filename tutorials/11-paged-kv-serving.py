"""Tutorial 11: paged KV cache — pool, page table, paged decode paths.

Parity: reference ``mega_triton_kernel/models/paged_kv_cache.py`` — a
page-pool cache with free-list allocation, consumed by its megakernel's
attention task through a page table.

TPU design: the pool is one array ``[L, P, Hkv, page, hd]``; the page
table rides as a scalar-prefetch operand and ``paged_flash_decode``
dereferences it — the grid step of sequence ``b``'s group ``g`` copies
pool pages ``table[b, 4g .. 4g + 3]`` up to its length, all of the KV
heads of each, of the layer addressed (the model's layer scan carries
the whole pool and hands the kernel ``layer=``), so attention reads
the pool in place and NO dense gather or per-layer slice ever
materializes. Three consumers share
the design: the model decode step (``decode_step`` dispatches on cache
type), ``Engine(paged=True)`` serving, and the megakernel (per-row page
DMAs in its attention block loop).
"""

from _common import setup

jax = setup()

import jax.numpy as jnp
import numpy as np

from triton_distributed_tpu.models import AutoLLM
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.models.paged_kv_cache import (
    init_paged_cache,
    write_prefill,
)
from triton_distributed_tpu.runtime.mesh import initialize_distributed


def main():
    ctx = initialize_distributed(tp=min(4, len(jax.devices())))
    model = AutoLLM.from_pretrained("tiny", ctx=ctx)

    # 1. The pool: pages are S-axis tiles; sequences own page LISTS
    #    (allocation is host-side control-plane work, per sequence).
    paged, pool = init_paged_cache(
        model.cfg, batch_size=2, ctx=ctx, max_length=64, page_size=16
    )
    print("pool pages:", paged.k_pages.shape[1],
          "table:", np.asarray(paged.page_table).tolist())

    # 2. Dense prefill per sequence, scattered into pages (one slice
    #    copy per page — jitted + donated, so the pool updates in place).
    dense1 = model.new_cache(1, 64)
    toks = jnp.asarray([5, 9, 2, 4, 8, 6, 7, 3], jnp.int32)
    logits, filled = model.prefill(toks, dense1, "xla")
    paged = write_prefill(paged, 0, filled.k, filled.v, len(toks))
    paged = write_prefill(paged, 1, filled.k, filled.v, len(toks))

    # 3. Paged decode: same decode_step entry point — the cache type
    #    selects the paged path (append through the table +
    #    paged_flash_decode over the pool).
    tok = jnp.argmax(logits)[None].repeat(2).astype(jnp.int32)
    logits_p, paged = model.decode_step(tok, paged, "xla")
    print("paged decode logits:", logits_p.shape)

    # 4. End-to-end: Engine(paged=True) serves identically to dense.
    prompt = np.asarray([[5, 9, 2, 4, 8, 6, 7, 3]] * 2, np.int32)
    out_d = Engine(model, temperature=0.0).serve(prompt, gen_len=4)
    out_p = Engine(model, temperature=0.0, paged=True, page_size=16).serve(
        prompt, gen_len=4
    )
    np.testing.assert_array_equal(out_d, out_p)
    print("paged serving matches dense token-for-token: OK")

    # 5. Quantized pool: kv_dtype="int8" stores int8 codes plus
    #    per-page-per-head scales, dequantized INSIDE the attention
    #    kernels — ~half the bf16 pool's bytes per cached token
    #    (docs/serving.md "Quantized KV cache").
    eng_q = Engine(model, temperature=0.0, paged=True, page_size=16,
                   kv_dtype="int8")
    eng_q.serve(prompt, gen_len=4)
    print("int8 pool:", eng_q.last_stats["kv_dtype"],
          "bytes/token:", eng_q.last_stats["kv_bytes_per_token"])


if __name__ == "__main__":
    main()
