"""Pallas TPU kernels for the fabric + serving hot spots.

Each kernel module holds the ``pl.pallas_call`` + BlockSpec; ``ops.py``
exposes the wrappers the dataplane calls (Mosaic on a TPU, the Pallas
interpreter elsewhere — decided per call); ``ref.py`` holds the pure-jnp
oracles the tests sweep against.
"""
