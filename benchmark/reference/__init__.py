"""The plain reference: GF(2^8) Reed-Solomon and Clay codes, the
SeaweedFS shard layout and the needle volume format, written from their
published descriptions.  Nothing here imports the program under test."""
