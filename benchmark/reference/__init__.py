"""Plain reference of the guarantees the cells state: a NumPy GF(2^8)
Reed-Solomon encode/decode (jerasure ``reed_sol_van``) and crc32c, with
tables and matrix construction of their own. Imports nothing from the
program under test."""
