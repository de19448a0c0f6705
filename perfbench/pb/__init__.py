"""The benchmark's own code: everything that decides a number lives here.

From the program (``ray_lightning_tpu``) the benchmark takes the system
under test and its spans and counters, nothing else. Importing this
package imports neither jax nor the program.
"""
