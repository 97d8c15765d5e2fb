"""The paper's storage technique generalized: LM serving over a paged KV
cache (GraphStore VID->LPN = sequence->page chains) with continuous
batching.  ``--pallas`` routes attention through the Pallas
decode_attention kernel (scalar-prefetched page tables; interpret on CPU).

  PYTHONPATH=src python examples/serve_lm_paged.py --requests 8
"""
from repro.compile_cache import use_compile_cache
from repro.launch.serve import main

if __name__ == "__main__":
    use_compile_cache()
    main()
