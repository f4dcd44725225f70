"""The lowered text of every serving cell's step and prefill programs at
the cell's rehearsal size, as a digest a program.

    python3 tests/lowered_texts.py [--root CHECKOUT] [--out FILE]

``--root`` is the checkout whose ``mxnet_tpu`` and ``benchmark`` are
lowered (this one by default); the output, JSON ``{program: {"sha256",
"chars"}}``, goes to ``--out`` or standard output.  Each decoder is
built as its cell's driver builds it, served by ``PagedSlots(...,
kernel="gather")`` with the cell's pool and buckets, and lowered for
the CPU (``lower_step`` / ``lower_prefill``): a program whose text is
unchanged computes what it did.  ``tests/test_lowered_programs.py``
compares this checkout's digests with those of the commit before the
gated-DeltaNet family (``tests/fixtures/lowered_programs.json``).
"""
import argparse
import hashlib
import json
import os
import sys

CELLS = ("serve_batch", "serve_batch_ling", "serve_block_sdar",
         "serve_docqa_kimi", "serve_longdoc_minimax")
SEED = 5


def _decoder(harness, cell, dtype):
    import jax.numpy as jnp

    from mxnet_tpu.models.decode import KVDecoder

    family = harness.load_family(cell.config["family"])
    srv = cell.traffic["server"]
    params = family.serving_weights(cell.config, SEED, dtype)
    if cell.config["family"] == "gpt2":
        c = family.sizes(cell.config)
        return KVDecoder(params, c["n_layer"], c["n_head"],
                         max_len=int(srv["max_len"]), dtype=dtype)
    return family.build_decoder(cell.config, params, int(srv["max_len"]),
                                jnp.dtype(dtype))


def digests(root, cells=CELLS):
    """``{"<cell>.step": ..., "<cell>.prefill_b<bucket>": ...}`` of
    ``cells``."""
    if root not in sys.path:
        sys.path.insert(0, root)
    import jax.numpy as jnp

    from benchmark import harness
    from mxnet_tpu.serving.paged_kv import PagedSlots

    out = {}
    for name in cells:
        cell = harness.resolve(name, rehearse=True, root=root)
        srv = cell.traffic["server"]
        dtype = jnp.dtype(cell.config["serving"]["weights_dtype"]) \
            if "serving" in cell.config else jnp.bfloat16
        decoder = _decoder(harness, cell, dtype)
        slots = PagedSlots(decoder, int(srv["num_slots"]),
                           block=int(srv["kv_block"]),
                           num_pages=srv.get("num_pages"),
                           prefill_buckets=tuple(srv["prefill_buckets"]),
                           kernel="gather")
        texts = {"step": slots.lower_step().as_text()}
        for bucket in srv["prefill_buckets"]:
            texts[f"prefill_b{bucket}"] = \
                slots.lower_prefill(bucket).as_text()
        for program, text in texts.items():
            out[f"{name}.{program}"] = {
                "sha256": hashlib.sha256(text.encode()).hexdigest(),
                "chars": len(text)}
        slots.pool = None
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    text = json.dumps(digests(os.path.abspath(args.root)), indent=1,
                      sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
