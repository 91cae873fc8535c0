"""A model family for the benchmark's tests only: the dense decoder of
``configs/decoder.py`` with a per-head RMSNorm on the queries and the keys
before their rotation, as the program computes it under ``qk_norm``.

It shows that a family the harness has never seen is served through it by
files alone: it reuses the decoder's sizes and FLOPs, adds the two norm
scales to each layer of its layout, turns on the program's ``qk_norm``, and
applies the norm in its reference.
"""

from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_testdata_decoder",
    Path(__file__).resolve().parents[1] / "configs" / "decoder.py")
decoder = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(decoder)

shape_of = decoder.shape_of
served_request = decoder.served_request      # a norm adds no matmul FLOPs


def layout(shape: dict) -> dict:
    tree = decoder.layout(shape)
    norm = jax.ShapeDtypeStruct((shape["layers"], shape["head_dim"]),
                                jnp.float32)
    tree["blocks"]["layer0"]["attn"].update(q_norm=norm, k_norm=norm)
    return tree


def program_config(base, shape: dict):
    return dataclasses.replace(decoder.program_config(base, shape),
                               qk_norm=True)


class Reference(decoder.Reference):
    def _project(self, a, h):
        q, k, v = super()._project(a, h)
        eps = self.s["norm_eps"]
        return (decoder._rms(q, a["q_norm"], eps),
                decoder._rms(k, a["k_norm"], eps), v)
