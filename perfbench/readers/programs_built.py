"""Executables built (compiled or fetched) inside the window: a count
from JAX's monitoring events, expected 0."""


def read(context):
    return context["programs_built"]
