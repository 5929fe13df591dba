"""Puts the benchmark's modules and the program's sources on the path."""
import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]
