"""Generation by diffusion over blocks (docs/SERVING.md "Block-diffusion
generation"): the pipeline of a model whose step yields no token, a few or a
whole block a row. The program it drives is
``ragged_model.build_block_step``; ``engine.decode_pipeline`` returns the
pipeline for a spec with ``causal_block > 1``."""

from deepspeed_tpu.inference.v2.blocks.pipeline import BlockDecodePipeline

__all__ = ["BlockDecodePipeline"]
