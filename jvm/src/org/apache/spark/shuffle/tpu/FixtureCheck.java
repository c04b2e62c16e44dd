package org.apache.spark.shuffle.tpu;

import java.io.IOException;
import java.nio.file.Files;
import java.nio.file.Path;
import java.nio.file.Paths;
import java.util.Arrays;
import java.util.LinkedHashMap;
import java.util.Map;

/**
 * Golden wire-format check: re-encodes every fixture frame with the SAME
 * builders {@link DaemonClient} uses on the wire and byte-compares against
 * jvm/fixtures/*.bin (generated by scripts/gen_shim_fixtures.py, asserted from
 * the Python side by tests/test_daemon.py). CI runs this after javac; a
 * protocol drift on either side fails one of the two checks.
 *
 * Usage: java org.apache.spark.shuffle.tpu.FixtureCheck path/to/jvm/fixtures
 */
public final class FixtureCheck {
  // Canonical parameters — scripts/gen_shim_fixtures.py uses the same literals.
  static final int SHUFFLE_ID = 7, NUM_MAPPERS = 4, NUM_REDUCERS = 8;
  static final int MAP_ID = 2, WRITER = 3, REDUCE_ID = 5;
  static final long FETCH_TAG = 0x1122334455667788L;
  static final int[] FETCH_MAPS = {0, 3}, FETCH_REDUCES = {5, 5};
  // AQE partial-map read (startMapIndex=1, endMapIndex=3): same wire shape as
  // a Spark 2.4 full-range read — see jvm/README.md "Spark 2.4 vs 3.x"
  static final int[] AQE_MAPS = {1, 2}, AQE_REDUCES = {REDUCE_ID, REDUCE_ID};
  // AQE coalesced read: reduce partitions 5..6 across every mapper 0..3 —
  // some of the cells are empty in the behavioral replay (tests/test_daemon.py)
  static final int[] COALESCE_MAPS = {0, 0, 1, 1, 2, 2, 3, 3};
  static final int[] COALESCE_REDUCES = {5, 6, 5, 6, 5, 6, 5, 6};
  // 11: several blocks of one writer in one WritePartition frame — reduce 5
  // in two entries (one partition continued), an empty block, reduce 6
  static final int[] BATCH_REDUCES = {1, 5, 5, 6};

  static byte[] writeBody() {
    byte[] b = new byte[256];
    for (int i = 0; i < 256; i++) b[i] = (byte) i;
    return b;
  }

  static byte[][] batchBodies() {
    byte[] first = new byte[16], last = new byte[32];
    for (int i = 0; i < 16; i++) first[i] = (byte) i;
    for (int i = 0; i < 32; i++) last[i] = (byte) (255 - i);
    return new byte[][] {first, writeBody(), new byte[0], last};
  }

  static Map<String, byte[]> frames() {
    Map<String, byte[]> m = new LinkedHashMap<>();
    m.put("01_create_shuffle.bin", DaemonClient.encodeFrame(
        DaemonClient.OP_CREATE_SHUFFLE,
        DaemonClient.headerCreateShuffle(SHUFFLE_ID, NUM_MAPPERS, NUM_REDUCERS), null));
    m.put("02_open_map_writer.bin", DaemonClient.encodeFrame(
        DaemonClient.OP_OPEN_MAP_WRITER,
        DaemonClient.headerOpenMapWriter(SHUFFLE_ID, MAP_ID), null));
    m.put("03_write_partition.bin", DaemonClient.encodeFrame(
        DaemonClient.OP_WRITE_PARTITION,
        DaemonClient.headerWritePartition(WRITER, REDUCE_ID), writeBody()));
    m.put("04_commit_map.bin", DaemonClient.encodeFrame(
        DaemonClient.OP_COMMIT_MAP, DaemonClient.headerCommitMap(WRITER), null));
    m.put("05_run_exchange.bin", DaemonClient.encodeFrame(
        DaemonClient.OP_RUN_EXCHANGE, DaemonClient.headerShuffleId(SHUFFLE_ID), null));
    m.put("06_fetch.bin", DaemonClient.encodeFrame(
        DaemonClient.OP_FETCH, null,
        DaemonClient.fetchRequestBody(FETCH_TAG, SHUFFLE_ID, FETCH_MAPS, FETCH_REDUCES)));
    m.put("07_remove_shuffle.bin", DaemonClient.encodeFrame(
        DaemonClient.OP_REMOVE_SHUFFLE, DaemonClient.headerShuffleId(SHUFFLE_ID), null));
    m.put("08_fetch_aqe_maprange.bin", DaemonClient.encodeFrame(
        DaemonClient.OP_FETCH, null,
        DaemonClient.fetchRequestBody(FETCH_TAG, SHUFFLE_ID, AQE_MAPS, AQE_REDUCES)));
    m.put("09_fetch_coalesced_empty.bin", DaemonClient.encodeFrame(
        DaemonClient.OP_FETCH, null,
        DaemonClient.fetchRequestBody(FETCH_TAG, SHUFFLE_ID, COALESCE_MAPS, COALESCE_REDUCES)));
    byte[][] batch = batchBodies();
    int[] batchLengths = new int[batch.length];
    for (int i = 0; i < batch.length; i++) batchLengths[i] = batch[i].length;
    m.put("11_write_partitions.bin", DaemonClient.encodeFrame(
        DaemonClient.OP_WRITE_PARTITION,
        DaemonClient.headerWritePartitions(WRITER, BATCH_REDUCES, batchLengths),
        DaemonClient.joinBlocks(batch)));
    return m;
  }

  /** Negative fixture 10: a header claiming one byte past MAX_FRAME_BYTES.
   * Asserts the Java limit constant matches the Python side's (the fixture's
   * declared body length is exactly limit + 1), that the shared reject
   * predicate refuses it (also via long-overflow lengths) while accepting a
   * frame at the limit.  Returns the failure count so a drift here is
   * REPORTED next to the positive-fixture drifts, not thrown over them. */
  static int checkOversizedFixture(Path dir) throws IOException {
    byte[] raw = Files.readAllBytes(dir.resolve("10_oversized_frame.bin"));
    if (raw.length != 20) {
      System.err.println(
          "MISMATCH: 10_oversized_frame.bin must be a bare 20 B header, got " + raw.length);
      return 1;
    }
    java.nio.ByteBuffer bb = java.nio.ByteBuffer.wrap(raw).order(java.nio.ByteOrder.LITTLE_ENDIAN);
    int op = bb.getInt();
    long hlen = bb.getLong();
    long blen = bb.getLong();
    int failures = 0;
    if (op != DaemonClient.OP_WRITE_PARTITION || hlen != 0) {
      System.err.println(
          "MISMATCH: 10_oversized_frame.bin shape drifted (op=" + op + ", hlen=" + hlen + ")");
      failures++;
    }
    if (blen != DaemonClient.MAX_FRAME_BYTES + 1) {
      System.err.println("MISMATCH: frame-limit drift: fixture declares " + blen
          + " B, Java limit + 1 is " + (DaemonClient.MAX_FRAME_BYTES + 1)
          + " — sync MAX_FRAME_BYTES with core/definitions.py");
      failures++;
    }
    if (!DaemonClient.frameTooLarge(hlen, blen)) {
      System.err.println("MISMATCH: oversized frame was NOT rejected by frameTooLarge");
      failures++;
    }
    if (!DaemonClient.frameTooLarge(1L << 62, 1L << 62)) {
      System.err.println("MISMATCH: overflowing lengths were NOT rejected by frameTooLarge");
      failures++;
    }
    if (DaemonClient.frameTooLarge(0, DaemonClient.MAX_FRAME_BYTES)) {
      System.err.println("MISMATCH: at-limit frame was wrongly rejected by frameTooLarge");
      failures++;
    }
    if (failures == 0) {
      System.out.println("ok: 10_oversized_frame.bin (rejected, limits in sync)");
    }
    return failures;
  }

  public static void main(String[] args) throws IOException {
    Path dir = Paths.get(args.length > 0 ? args[0] : "jvm/fixtures");
    int failures = 0;
    for (Map.Entry<String, byte[]> e : frames().entrySet()) {
      byte[] expected = Files.readAllBytes(dir.resolve(e.getKey()));
      if (!Arrays.equals(expected, e.getValue())) {
        System.err.println("MISMATCH: " + e.getKey()
            + " (fixture " + expected.length + " B, encoder " + e.getValue().length + " B)");
        failures++;
      } else {
        System.out.println("ok: " + e.getKey());
      }
    }
    failures += checkOversizedFixture(dir);
    if (failures > 0) {
      System.err.println(failures + " fixture(s) drifted from the Java encoder");
      System.exit(1);
    }
    System.out.println("all " + (frames().size() + 1) + " wire fixtures match");
  }
}
