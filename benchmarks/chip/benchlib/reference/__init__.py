"""Plain reference of one HSFL simulation (arXiv:2306.09484, Sec. IV).

Written from the paper's equations and the configuration file alone: it
imports nothing of the program under test and takes none of its arrays.
The simulated environment (digits, client shards, UAV fleet, device
compute, batch draws) is drawn from the same seeded streams the program's
contract names, so the same seed gives the same environment on both sides.
"""
