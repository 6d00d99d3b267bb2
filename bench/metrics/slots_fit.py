"""Slots the program's ``fit_slots_and_pool`` admits on the chip for the
cell's max_len (halving from the mix's ceiling until the largest serving
program fits the program's share of HBM)."""


def read(run):
    return float(run.setup.n_slots)
