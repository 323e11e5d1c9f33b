"""A decoder-only LM served through ``TransformerDecoderLM`` ->
``ModelRepository.add_decoder`` -> ``ModelServer.generate`` (paged-KV
``DecodeEngine``), a copy of ``chip_smoke.py``'s construction."""
import gc

import jax.numpy as jnp


def _to_program(weights, dims, pos):
    """canonical leaves (perfbench/reference/decoder_lm.py) -> the dict
    ``paged_prefill`` / ``paged_decode_step`` consume."""
    cells = [{leaf: weights[f"l{i}.{leaf}"]
              for leaf in ("n1_g", "n1_b", "qkv_w", "qkv_b", "o_w", "o_b",
                           "n2_g", "n2_b", "f1_w", "f1_b", "f2_w", "f2_b")}
             for i in range(dims["num_layers"])]
    return {"embed": weights["embed"], "pos": pos,
            "fn_g": weights["fn_g"], "fn_b": weights["fn_b"],
            "proj_w": weights["proj_w"], "proj_b": weights["proj_b"],
            "cells": cells}


class Program:
    def __init__(self, cfg, dims, serving, weights):
        import mxnet_tpu as mx
        from mxnet_tpu import serving as sv
        from mxnet_tpu.models import TransformerDecoderLM
        mx.random.seed(0)       # same initialiser programs every run
        lm = TransformerDecoderLM(
            vocab_size=dims["vocab_size"], units=dims["units"],
            hidden_size=dims["hidden_size"], num_layers=dims["num_layers"],
            num_heads=dims["num_heads"], max_length=dims["max_length"],
            activation=cfg["activation"])
        lm.initialize()
        repo = sv.ModelRepository()
        repo.add_decoder("lm", lm)
        self.adapter = repo.get("lm").decode_model
        served = jnp.dtype(cfg["precision"]["params"])
        self.adapter.params = _to_program(
            {k: v.astype(served) for k, v in weights.items()}, dims,
            self.adapter.params["pos"])
        self.server = sv.ModelServer(repo, sv.ServingConfig(
            decode_page_size=serving["page_size"],
            decode_pool_pages=serving["pool_pages"],
            decode_max_batch=serving["max_batch"],
            prefix_cache=serving["prefix_cache"]))

    def generate(self, prompt, new_tokens, on_token, timeout):
        return self.server.generate("lm", prompt, max_new_tokens=new_tokens,
                                    on_token=on_token, timeout=timeout)

    def programs(self):
        return self.adapter.programs()

    def free(self):
        self.server.stop()
        self.server = self.adapter = None
        gc.collect()


def build(cfg, dims, serving, weights):
    return Program(cfg, dims, serving, weights)
