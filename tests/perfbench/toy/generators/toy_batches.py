"""A generator that exists only in the tests' temporary copy: the shipped
``train_batches`` under another name, to show one is found by its file."""
from perfbench import loader


def generate(params, seed, seconds, limits):
    return loader.load_module("generators", "train_batches").generate(
        params, seed, seconds, limits)
