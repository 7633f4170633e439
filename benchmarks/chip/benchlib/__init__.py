"""Library of the chip benchmark: harness, engines, trace reduction and the
plain reference that decides ``correct``.  Nothing here is imported by the
program under test."""
